"""Plan-served GAN inference engine: bucketed dynamic batching over
precompiled :class:`~repro.kernels.plan.TconvPlan`s.

PRs 1-4 made a generator *call* cheap (unified kernel, compile-once plans,
fused epilogues); this module makes a generator *service* cheap. The
deployment setting is the one HUGE^2 (arXiv:1907.11210) and GANAX
(arXiv:1806.01107) target — GAN generators under sustained request traffic
— and the design leans on exactly what the plan layer guarantees: a
``TconvPlan`` is keyed on its batch size, so a **fixed set of batch
buckets** means a fixed set of executables and zero steady-state retraces.

The loop is the classic dynamic-batching triangle:

1. **warmup** — for every registered model and every policy bucket, compile
   the whole-generator plan (:func:`~repro.kernels.plan.compile_plan_buckets`,
   fused epilogues included) and trace+compile one jitted executable. Every
   compile increments the metrics recompile counter *at trace time*, so a
   flat counter after warmup is machine-checkable proof of zero retraces.
2. **admit** — requests (each ``n`` latent rows for one model) enter a
   per-model FIFO queue, or are rejected with
   :class:`~repro.serve.batching.QueueFull` when the queued-sample bound is
   exceeded (backpressure: bounded queueing latency under overload).
3. **bucket + execute + recycle** — the step loop serves the model whose
   head request is oldest, packs whole head-of-queue requests into the
   smallest bucket that holds them (pad-and-mask: the batch is padded with
   zero rows up to the bucket, pad rows are sliced off the output), runs
   the precompiled executable, and hands each request its contiguous slice.
   A max-wait deadline flushes partial batches so light traffic is not
   held hostage to batch formation.

Single-host reference runtime, same status as the LM
:class:`~repro.serve.engine.ServeEngine` next door: the batching loop is
synchronous Python around jitted executables. At production scale the same
executables run under ``shard_plan_apply`` with the bucket batch sharded
over the data axes — the policy/metrics layers are unchanged.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs
from repro.obs.timeline import TimelineStore
from repro.serve.batching import BucketPolicy, QueueFull
from repro.serve.metrics import ServeMetrics


@dataclasses.dataclass
class GenRequest:
    """One generation request: ``n`` latent rows for one registered model.

    ``deadline_s`` (optional) is the request's maximum queueing+service
    budget, in seconds from admission. A request still queued when its
    deadline passes is **expired**: dropped at the next step, counted in
    ``metrics.expired``, and left ``done=False`` with ``expired=True`` —
    the client is told, never silently handed stale output it has already
    given up waiting for.

    Every request the engine touches reaches exactly one **terminal
    state** (the conservation invariant — nothing is silently lost):

    * ``done`` — served; ``output`` holds the generated samples.
    * ``expired`` — deadline passed while queued; never dispatched.
    * ``rejected`` — refused at admission (backpressure ``QueueFull``).
    * ``failed`` — admitted but terminally unservable (malformed in
      replay mode, retry budget exhausted, or shed with every replica
      dead under the :class:`~repro.serve.supervisor.ReplicaSupervisor`).

    ``t_done`` is stamped at completion AND at expiry/failure, so
    ``latency_s`` (admission → terminal resolution) is measurable for
    every resolved request, not just served ones. ``retries`` counts
    dispatch attempts beyond the first; ``replica`` records which replica
    (or ``"inline"`` fallback) served the request, when a supervisor did,
    and ``bucket`` the batch bucket it was served in.
    """

    model: str
    z: object                  # (n, z_dim) latents
    deadline_s: float | None = None
    # filled by the engine:
    rid: int = -1
    t_submit: float = 0.0
    t_done: float = 0.0
    output: object = None      # (n, H, W, C) on completion
    done: bool = False
    expired: bool = False
    rejected: bool = False
    failed: bool = False
    retries: int = 0
    replica: str | None = None
    bucket: int = 0            # batch bucket of the dispatch that served it

    @property
    def n(self) -> int:
        return int(np.shape(self.z)[0])

    @property
    def terminal_state(self) -> str | None:
        """``"done" | "expired" | "rejected" | "failed"`` — or None while
        the request is still pending. Raises if the engine ever left the
        request in more than one terminal state (a conservation bug)."""
        states = [s for s in ("done", "expired", "rejected", "failed")
                  if getattr(self, s)]
        if len(states) > 1:
            raise AssertionError(
                f"request {self.rid} in {len(states)} terminal states: "
                f"{states}"
            )
        return states[0] if states else None

    @property
    def latency_s(self) -> float:
        """Admission → terminal resolution. For served requests this is the
        classic completion latency; for expired ones it is the queue
        residence at purge (``t_done`` is stamped then too)."""
        if self.done or self.expired or self.failed or self.rejected:
            return self.t_done - self.t_submit
        return float("nan")


@dataclasses.dataclass
class _ModelSlot:
    cfg: object
    params: object
    plans: dict = dataclasses.field(default_factory=dict)   # bucket -> plan
    apply: dict = dataclasses.field(default_factory=dict)   # bucket -> jit fn
    queue: deque = dataclasses.field(default_factory=deque)


class GanEngine:
    """Bucketed dynamic-batching engine over plan-compiled generators.

    ``clock`` is injectable (tests drive the deadline logic with a fake
    clock); everything else is plain state: a registry of model slots, a
    policy, and a metrics sink.
    """

    def __init__(self, policy: BucketPolicy | None = None, *,
                 dtype="float32", train: bool = False, fuse="auto",
                 clock=time.monotonic, recorder=None):
        self.policy = policy or BucketPolicy()
        self.dtype = str(jnp.dtype(dtype))
        self.train = train
        self.fuse = fuse   # layer-pair megafusion: "auto" | "force" | "off"
        self.clock = clock
        self.metrics = ServeMetrics()
        self.registry: dict[str, _ModelSlot] = {}
        self.completed: list[GenRequest] = []   # completion order
        self.warmup_recompiles: int | None = None
        self._rid = itertools.count()
        # Observability (docs/OBSERVABILITY.md): per-request lifecycle
        # timelines, populated only while tracing is enabled; an optional
        # flight recorder shadows terminal anomalies regardless of the flag.
        self.timeline = TimelineStore()
        self.recorder = recorder

    def _tl(self, rid, event: str, t: float, *, model=None, **attrs) -> None:
        """Record one request-lifecycle edge — one flag check when off."""
        if not obs.enabled():
            return
        self.timeline.event(rid, event, t, model=model, **attrs)

    # ----------------------------------------------------------- registry

    def register(self, cfg, params, *, name: str | None = None) -> str:
        """Add one generator (config + trained params) to the engine. Call
        for each zoo member to be served, then :meth:`warmup` once."""
        name = name or cfg.name
        if name in self.registry:
            raise ValueError(f"model {name!r} already registered")
        self.registry[name] = _ModelSlot(cfg=cfg, params=params)
        return name

    def warmup(self, registry_path=None) -> None:
        """Compile every (model, bucket) executable up front: plans via
        :func:`~repro.kernels.plan.compile_plan_buckets`, then one traced+
        compiled jit call each on zero latents. After this returns, the
        metrics recompile counter is frozen at its warmup value
        (:attr:`warmup_recompiles`) — steady-state serving adds zero.

        ``registry_path`` is the warm start
        (:mod:`repro.kernels.plan_registry`, written by :meth:`save_plans`):
        every ``"{model}:{bucket}"`` plan found in the file is adopted
        verbatim — no per-process autotune-cache consult, no fusion-pass
        re-resolution — and only (model, bucket) combinations the registry
        lacks compile the normal way."""
        if registry_path is not None:
            from repro.kernels.plan_registry import load_plan_registry

            reg = load_plan_registry(registry_path)
            for name, slot in self.registry.items():
                for bucket in self.policy.buckets:
                    plan = reg.get(f"{name}:{bucket}")
                    if plan is not None:
                        slot.plans[bucket] = plan
        for name, slot in self.registry.items():
            for bucket in self.policy.buckets:
                fn = self._executable(name, bucket)
                z0 = jnp.zeros((bucket, slot.cfg.z_dim), self.dtype)
                jax.block_until_ready(fn(slot.params, z0))
        self.warmup_recompiles = self.metrics.recompiles

    def save_plans(self, path) -> None:
        """Persist every compiled (model, bucket) plan to ``path`` as a plan
        registry (:mod:`repro.kernels.plan_registry`) under
        ``"{model}:{bucket}"`` keys — the artifact
        :meth:`warmup(registry_path=...) <warmup>` warm-starts from."""
        from repro.kernels.plan_registry import save_plan_registry

        save_plan_registry(
            {
                f"{name}:{bucket}": plan
                for name, slot in self.registry.items()
                for bucket, plan in slot.plans.items()
            },
            path,
        )

    def _executable(self, name: str, bucket: int):
        """The jitted whole-generator executable for one (model, bucket).

        Built lazily so an un-warmed engine still serves correctly (it just
        pays the compile inline — and the recompile counter shows it: the
        counting call sits INSIDE the traced body, so it fires once per
        trace and never on a jit-cache hit)."""
        slot = self.registry[name]
        fn = slot.apply.get(bucket)
        if fn is None:
            from repro.kernels.plan import compile_plan_buckets
            from repro.models.gan import generator_apply, generator_epilogues

            if bucket not in slot.plans:
                slot.plans.update(compile_plan_buckets(
                    slot.cfg, [bucket], self.dtype, train=self.train,
                    epilogues=generator_epilogues(slot.cfg),
                    fuse=self.fuse,
                ))
            plan = slot.plans[bucket]
            cfg, metrics = slot.cfg, self.metrics

            def run(params, z):
                metrics.count_recompile()   # trace-time side effect only
                return generator_apply(params, cfg, z, plan=plan)

            fn = slot.apply[bucket] = jax.jit(run)
        return fn

    # ---------------------------------------------------------- admission

    @property
    def queued_samples(self) -> int:
        return sum(r.n for s in self.registry.values() for r in s.queue)

    @property
    def queued_requests(self) -> int:
        return sum(len(s.queue) for s in self.registry.values())

    def submit(self, req: GenRequest) -> int:
        """Admit one request (FIFO per model). Raises :class:`QueueFull`
        when the queued-sample bound would be exceeded (backpressure) and
        ``ValueError`` for malformed requests — a request must fit a single
        dispatch (``n <= max_bucket``; split client-side to go bigger)."""
        slot = self.registry.get(req.model)
        if slot is None:
            raise ValueError(
                f"model {req.model!r} not registered "
                f"(have {sorted(self.registry)})"
            )
        n = req.n
        if np.ndim(req.z) != 2 or np.shape(req.z)[1] != slot.cfg.z_dim:
            raise ValueError(
                f"z must be (n, {slot.cfg.z_dim}), got {np.shape(req.z)}"
            )
        if n < 1:
            raise ValueError("request must carry at least one latent row")
        if n > self.policy.max_bucket:
            raise ValueError(
                f"request of {n} samples exceeds the largest bucket "
                f"{self.policy.max_bucket}; split it client-side"
            )
        if req.deadline_s is not None and req.deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be positive, got {req.deadline_s}"
            )
        if self.queued_samples + n > self.policy.max_queue:
            req.rejected = True
            req.t_submit = req.t_done = self.clock()
            self.metrics.record_reject(req.model)
            # no rid was assigned (backpressure precedes assignment, pinned
            # by the rid==-1 test) — timeline under a synthetic id
            self._tl(f"reject#{self.metrics.rejected}", "reject", req.t_done,
                     model=req.model, n=n)
            obs.counter("serve.rejected")
            raise QueueFull(
                f"queue holds {self.queued_samples} samples, request of {n} "
                f"exceeds max_queue={self.policy.max_queue}"
            )
        req.rid = next(self._rid)
        req.t_submit = self.clock()
        self.metrics.record_admit(req.t_submit, req.model)
        slot.queue.append(req)
        self._tl(req.rid, "admit", req.t_submit, model=req.model, n=n,
                 deadline_s=req.deadline_s)
        self._tl(req.rid, "queue", req.t_submit, depth=len(slot.queue),
                 queued_samples=self.queued_samples)
        obs.counter("serve.admitted")
        return req.rid

    # --------------------------------------------------------------- step

    def _purge_expired(self, now: float) -> int:
        """Drop queued requests whose deadline has passed (anywhere in the
        queue — deadlines are per-request, so a fresh short-deadline request
        can expire behind a patient head). Runs before every dispatch
        decision, so an expired request is never packed into a batch."""
        dropped = 0
        for name, slot in self.registry.items():
            if not any(
                r.deadline_s is not None
                and now - r.t_submit > r.deadline_s
                for r in slot.queue
            ):
                continue
            keep = deque()
            for r in slot.queue:
                if (r.deadline_s is not None
                        and now - r.t_submit > r.deadline_s):
                    r.expired = True
                    r.t_done = now   # stamp: time-to-expiry is measurable
                    self.metrics.record_expired(
                        now, residence_s=now - r.t_submit, model=name
                    )
                    self._tl(r.rid, "expire", now, model=name,
                             residence_s=now - r.t_submit)
                    obs.counter("serve.expired")
                    dropped += 1
                else:
                    keep.append(r)
            slot.queue = keep
        return dropped

    def _next_model(self) -> str | None:
        """FIFO fairness across models: serve whichever queue's HEAD request
        is oldest (per-queue order is already FIFO)."""
        best, best_t = None, None
        for name, slot in self.registry.items():
            if slot.queue and (best_t is None
                               or slot.queue[0].t_submit < best_t):
                best, best_t = name, slot.queue[0].t_submit
        return best

    def step(self, now: float | None = None, *, drain: bool = False) -> bool:
        """One batching-loop iteration: pick the model with the oldest head
        request, dispatch if the policy says flush (``drain=True`` forces a
        flush — used when no more arrivals are coming). Returns whether a
        batch ran."""
        if now is None:
            now = self.clock()
        self._purge_expired(now)
        name = self._next_model()
        if name is None:
            return False
        slot = self.registry[name]
        sizes = [r.n for r in slot.queue]
        if not drain and not self.policy.should_flush(
            sizes, now - slot.queue[0].t_submit
        ):
            return False
        count, bucket = self.policy.pack(sizes)
        reqs = [slot.queue.popleft() for _ in range(count)]
        self._execute(name, reqs, bucket)
        return True

    def _pack_latents(self, reqs: list, bucket: int):
        """Concatenate the requests' latents and pad with zero rows up to
        the bucket. Returns ``(z, n_real)`` with ``z`` a host array of
        ``bucket`` rows."""
        with obs.span("serve.pack", bucket=bucket, reqs=len(reqs)):
            z = np.concatenate(
                [np.asarray(r.z, dtype=self.dtype) for r in reqs], axis=0
            )
            n_real = z.shape[0]
            if n_real < bucket:
                z = np.concatenate(
                    [z, np.zeros((bucket - n_real, z.shape[1]), z.dtype)],
                    axis=0,
                )
        if obs.enabled():
            t = self.clock()
            for r in reqs:
                self._tl(r.rid, "pack", t, model=r.model, bucket=bucket,
                         n_real=n_real)
        return z, n_real

    def _finalize(self, name: str, reqs: list, out, n_real: int,
                  bucket: int, t0: float, *, replica: str | None = None) -> None:
        """Complete a dispatched batch: record it, slice each request's
        contiguous rows back out (the mask is the slice — pad rows never
        reach a client), and mark every request done."""
        now = self.clock()
        self.metrics.record_batch(n_real, bucket, now - t0, now, model=name)
        with obs.span("serve.slice", model=name, reqs=len(reqs)):
            row = 0
            for r in reqs:
                r.output = out[row : row + r.n]
                row += r.n
                r.done = True
                r.t_done = now
                r.replica = replica
                r.bucket = bucket
                self.metrics.record_completion(r.latency_s, model=name)
                self.completed.append(r)
                self._tl(r.rid, "slice", now, model=name, rows=r.n)
                self._tl(r.rid, "reply", now, model=name,
                         latency_s=r.latency_s, replica=replica)
        obs.counter("serve.completed", len(reqs))
        obs.observe("serve.batch_wall_s", now - t0)

    def _execute(self, name: str, reqs: list, bucket: int) -> None:
        """Pad-and-mask dispatch: pack the requests' latents up to the
        bucket, run the precompiled executable, hand each request its
        slice. The :class:`~repro.serve.supervisor.ReplicaSupervisor`
        overrides this method (same pack/finalize helpers) to route the
        packed bucket through health-checked replicas instead."""
        slot = self.registry[name]
        z, n_real = self._pack_latents(reqs, bucket)
        t0 = self.clock()
        if obs.enabled():
            for r in reqs:
                self._tl(r.rid, "dispatch", t0, model=name, bucket=bucket)
        with obs.span("serve.dispatch", model=name, bucket=bucket,
                      n_real=n_real):
            out = self._executable(name, bucket)(slot.params, jnp.asarray(z))
            out = np.asarray(jax.block_until_ready(out))
        self._finalize(name, reqs, out, n_real, bucket, t0)

    # -------------------------------------------------------- conservation

    def conservation(self) -> dict:
        """The terminal-state ledger (see :class:`GenRequest`): every
        admitted request must be done, expired, failed, or still queued —
        ``ok`` is False iff requests went missing (or were double-counted).
        Rejected/malformed requests were refused at admission and are
        reported alongside."""
        c = self.metrics.conservation()
        c["queued"] = self.queued_requests
        c["ok"] = c["admitted"] == c["resolved"] + c["queued"]
        return c

    # ---------------------------------------------------------------- run

    def serve(self, requests, *, drain: bool = True) -> list:
        """Burst mode: submit everything, then run the batching loop to
        completion. Raises :class:`QueueFull` if the burst overflows the
        queue bound (size ``max_queue`` bursts are admission-safe)."""
        for r in requests:
            self.submit(r)
        while self.step(drain=drain):
            pass
        return requests

    def replay(self, requests, arrivals_s, *, sleep=time.sleep) -> list:
        """Trace-replay mode: submit each request when the wall clock passes
        its arrival offset (seconds from replay start), batching between
        arrivals under the live policy (deadline flushes included), then
        drain. ``requests`` and ``arrivals_s`` are parallel sequences;
        arrivals must be sorted ascending. A live trace must keep serving
        through bad requests, so admission errors never abort the replay:
        a request rejected with :class:`QueueFull` is shed (``rejected``,
        counted in ``metrics.rejected``) and a **malformed** request
        (unknown model, bad latent shape — ``ValueError`` from
        :meth:`submit`) is recorded as terminally ``failed`` and counted
        in ``metrics.malformed``, while the rest of the trace is served."""
        order = list(zip(requests, arrivals_s))
        if any(b < a for (_, a), (_, b) in zip(order, order[1:])):
            raise ValueError("arrivals_s must be sorted ascending")
        t0 = self.clock()
        i = 0
        while i < len(order) or self.queued_requests:
            now = self.clock() - t0
            while i < len(order) and order[i][1] <= now:
                req = order[i][0]
                try:
                    self.submit(req)
                except QueueFull:
                    pass   # shed: request marked rejected by submit
                except ValueError:
                    # malformed: count it, fail it, keep serving the trace
                    req.failed = True
                    req.t_submit = req.t_done = self.clock()
                    self.metrics.record_malformed(
                        getattr(req, "model", None)
                    )
                    self._tl(f"malformed#{self.metrics.malformed}", "fail",
                             req.t_done, model=getattr(req, "model", None),
                             reason="malformed")
                    obs.counter("serve.malformed")
                i += 1
            if self.step():
                continue
            if i < len(order):   # idle until the next arrival or deadline
                wait = order[i][1] - (self.clock() - t0)
                if self.queued_requests:
                    wait = min(wait, self.policy.max_wait_s)
                if wait > 0:
                    sleep(min(wait, 1e-3))
            elif self.queued_requests:
                self.step(drain=True)   # no more arrivals: flush the tail
        return requests


def sequential_executables(cfg, params, sizes, *, dtype="float32",
                           train: bool = False, fuse="auto") -> dict:
    """Warmed plan-compiled per-size executables ``{n: fn(params, z)}`` —
    the **sequential per-request dispatch baseline** the serving benchmark
    and example compare the bucketed engine against. Each callable runs the
    whole generator at exactly batch ``n`` (no padding, fused epilogues,
    plan precompiled and traced on zero latents), so the baseline pays only
    true per-request dispatch cost — the strongest unbatched opponent the
    repo can field."""
    from repro.kernels.plan import compile_plan_buckets
    from repro.models.gan import generator_apply, generator_epilogues

    plans = compile_plan_buckets(
        cfg, sizes, dtype, train=train, epilogues=generator_epilogues(cfg),
        fuse=fuse,
    )
    fns = {}
    for n, plan in plans.items():

        def run(p, z, _plan=plan):
            return generator_apply(p, cfg, z, plan=_plan)

        fn = jax.jit(run)
        jax.block_until_ready(fn(params, jnp.zeros((n, cfg.z_dim), dtype)))
        fns[n] = fn
    return fns
