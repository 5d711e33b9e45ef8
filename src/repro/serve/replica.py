"""A serving replica: one warmed set of per-(model, bucket) executables.

The :class:`~repro.serve.gan_engine.GanEngine` owns admission, bucketing,
and fairness; a :class:`Replica` owns **execution** — its own compiled
plans, its own jitted executables, its own trace-time recompile counter.
The :class:`~repro.serve.supervisor.ReplicaSupervisor` routes packed
buckets across a set of replicas, which is what turns the single
synchronous engine loop into a unit that survives a replica hang, crash,
or poisoned output (the serving-side failure model of
:mod:`repro.distributed.fault_tolerance`).

Two properties make the replica the right isolation boundary:

* **Executables are per-replica.** Each replica jit-compiles its own
  closures over the same immutable plans, so replicas never share a trace
  and ``replica.recompiles`` is a per-replica zero-steady-state-retraces
  invariant (the supervisor test pins it under injected faults: a retried
  bucket re-runs an already-warmed executable, never a fresh trace).
* **Dispatch has one narrow seam.** Every device interaction — real
  dispatches and health probes alike — passes through the injectable
  ``dispatch_hook`` *before* the executable runs. The serving chaos
  harness (:mod:`repro.serve.fault_injection`) lives entirely on that
  seam: crash-at-dispatch-N, hang past the timeout, transient errors, and
  NaN output planes are all injected there, deterministically, without the
  production path importing the harness.

Single-device by default; ``shard=True`` routes every executable through
:func:`repro.distributed.sharding.shard_plan_apply`, so one replica can
span a ``(pod, data)`` mesh slice (plans are static — the sharded
generator still traces exactly once per bucket) and degrades unsharded
when no mesh is available, like every other helper in the repo.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs
from repro.serve.gan_engine import readback


@dataclasses.dataclass
class _ReplicaModel:
    cfg: object
    params: object
    plans: dict = dataclasses.field(default_factory=dict)   # bucket -> plan
    apply: dict = dataclasses.field(default_factory=dict)   # bucket -> jit fn


class Replica:
    """One serving replica: warmed per-(model, bucket) executables behind a
    narrow injectable dispatch seam.

    ``dispatch_hook(replica, index, model, bucket, probe=...)`` — when set —
    is called before every dispatch (``index`` counts this replica's real
    dispatches from 1) and every probe (``probe=True``, ``index`` counts
    probes). It may raise (the supervisor treats any exception from a
    dispatch as a replica failure) or return a callable that transforms the
    host output array (how the chaos harness poisons an output plane).

    ``clock`` is only used by the hook side of the seam indirectly (fault
    injection advances the engine's injected clock); **baselines** —
    the per-(model, bucket) post-warmup step walls the supervisor derives
    dispatch timeouts from — are always measured with
    ``time.perf_counter``, because they are real device measurements, not
    scheduler state.
    """

    def __init__(self, replica_id: str, *, dtype="float32",
                 train: bool = False, fuse="auto", shard: bool = False,
                 mesh=None, dispatch_hook=None):
        self.replica_id = str(replica_id)
        self.dtype = str(jnp.dtype(dtype))
        self.train = train
        self.fuse = fuse
        self.shard = shard
        self.mesh = mesh
        self.dispatch_hook = dispatch_hook
        self.registry: dict[str, _ReplicaModel] = {}
        self.recompiles = 0        # per-replica trace-time counter
        self.dispatches = 0        # real dispatches through the seam
        self.probe_count = 0       # probes through the seam
        self.baseline_s: dict = {}  # (model, bucket) -> warmed step wall

    # ----------------------------------------------------------- registry

    def register(self, cfg, params, *, name: str | None = None) -> str:
        name = name or cfg.name
        if name in self.registry:
            raise ValueError(
                f"model {name!r} already registered on replica "
                f"{self.replica_id!r}"
            )
        self.registry[name] = _ReplicaModel(cfg=cfg, params=params)
        return name

    def warmup(self, buckets) -> None:
        """Compile every (model, bucket) executable and measure its warmed
        step wall (``baseline_s``): one call to trace+compile, one timed
        call on the compiled executable — the tuned-plan step time the
        supervisor's per-batch dispatch timeouts derive from."""
        for name, slot in self.registry.items():
            for bucket in buckets:
                fn = self._executable(name, bucket)
                z0 = jnp.zeros((bucket, slot.cfg.z_dim), self.dtype)
                jax.block_until_ready(fn(slot.params, z0))   # compile
                t0 = time.perf_counter()
                jax.block_until_ready(fn(slot.params, z0))   # measure
                self.baseline_s[(name, bucket)] = time.perf_counter() - t0

    def _executable(self, name: str, bucket: int):
        """The jitted whole-generator executable for one (model, bucket),
        compiled lazily (an un-warmed replica still serves — its recompile
        counter shows the inline compile, exactly like the engine's)."""
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
            cfg = slot.cfg

            def apply_fn(p, z, pl):
                return generator_apply(p, cfg, z, plan=pl)

            if self.shard:
                from repro.distributed.sharding import shard_plan_apply

                mesh = self.mesh

                def run(params, z):
                    self._note_recompile()   # trace-time side effect only
                    return shard_plan_apply(apply_fn, params, z, plan,
                                            mesh=mesh)
            else:

                def run(params, z):
                    self._note_recompile()   # trace-time side effect only
                    return apply_fn(params, z, plan)

            fn = slot.apply[bucket] = jax.jit(run)
        return fn

    def _note_recompile(self) -> None:
        self.recompiles += 1

    # ----------------------------------------------------------- dispatch

    def execute(self, name: str, z, bucket: int) -> np.ndarray:
        """Run one packed bucket. ``z`` is the already-padded ``(bucket,
        z_dim)`` latent batch; returns the host output array. The dispatch
        seam fires first — any exception it raises is this replica failing
        the dispatch — and its optional output transform is applied to the
        host array before returning (never to what other replicas see)."""
        self.dispatches += 1
        with obs.span("replica.execute", replica=self.replica_id,
                      model=name, bucket=bucket):
            transform = None
            if self.dispatch_hook is not None:
                transform = self.dispatch_hook(
                    self, self.dispatches, name, bucket, probe=False
                )
            slot = self.registry[name]
            out = self._executable(name, bucket)(slot.params, jnp.asarray(z))
            out = readback(out)
            if transform is not None:
                out = transform(out)
            return out

    def probe(self) -> bool:
        """Health probe: run the smallest-bucket executable of the first
        registered model on zero latents through the dispatch seam. Returns
        whether the output came back finite; raises if the replica (or the
        injected fault occupying it) refuses the dispatch. The supervisor
        treats False and an exception identically — probe failed."""
        if not self.registry:
            raise RuntimeError(
                f"replica {self.replica_id!r} has no registered models"
            )
        name, slot = next(iter(self.registry.items()))
        bucket = min(slot.apply) if slot.apply else 1
        self.probe_count += 1
        with obs.span("replica.probe", replica=self.replica_id,
                      model=name, bucket=bucket):
            transform = None
            if self.dispatch_hook is not None:
                transform = self.dispatch_hook(
                    self, self.probe_count, name, bucket, probe=True
                )
            z0 = jnp.zeros((bucket, slot.cfg.z_dim), self.dtype)
            out = self._executable(name, bucket)(slot.params, z0)
            out = readback(out)
            if transform is not None:
                out = transform(out)
            return bool(np.isfinite(out).all())

    def describe(self) -> str:
        return (
            f"replica {self.replica_id}: {len(self.registry)} models, "
            f"{sum(len(m.apply) for m in self.registry.values())} "
            f"executables, {self.dispatches} dispatches, "
            f"{self.probe_count} probes, {self.recompiles} compiles"
        )
