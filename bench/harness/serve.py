"""Serving cells: the harness's load generator drives ``GanEngine``.

The loop is the one of ``GanEngine.replay``: submit what is due, let the
engine step (pack, pad, dispatch, slice), sleep briefly when there is
nothing to do. Open mixes time each request from its due time; closed
mixes send a client's next request when its reply arrives. Each output is
dropped as soon as its latency is recorded, except for the requests the
seed picked for the correctness comparison.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time

import numpy as np

from harness import loadgen, system
from harness import trace as tracelib

# a request still unanswered this long after the window closed has failed
DRAIN_LIMIT_S = 60.0
# rows per call of the reference generator: one compiled shape
REFERENCE_ROWS = 16


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_serving(run, t_start: float, trace: bool) -> list:
    """Set up, measure, check. Fills ``run`` and returns the compared
    answers' latents (for the control script)."""
    import jax

    system.import_program()
    from repro.obs import trace as obs
    from repro.serve import BucketPolicy, GanEngine, GenRequest

    cfg, mix = run.cell.config, run.cell.traffic
    ref = system.reference(cfg)
    run.notes["setup_start_s"] = time.perf_counter() - t_start
    params = jax.block_until_ready(
        system.generator_weights(ref, cfg, run.seed))
    run.notes["setup_weights_s"] = time.perf_counter() - t_start
    engine = GanEngine(BucketPolicy(buckets=tuple(mix["buckets"])),
                       dtype=cfg["dtype"], clock=time.perf_counter)
    model = engine.register(system.program_config(cfg), params)
    engine.warmup()
    run.notes["setup_warmup_s"] = time.perf_counter() - t_start
    model_cfg = engine.registry[model].cfg
    for bucket in engine.policy.buckets:
        # one whole dispatch per bucket, copy to the host included, so
        # that nothing is done for the first time inside the window
        engine.serve([GenRequest(model, np.zeros((bucket, model_cfg.z_dim),
                                                 np.float32))])
    engine.completed.clear()
    loop = OpenLoop if mix["kind"] == "open" else ClosedLoop
    if mix.get("warmup_s"):
        # the mix's own traffic for a while, on a schedule of its own, so
        # that the host path is past its first-use costs when the window
        # opens
        loop(engine, model, mix, run.seed + 2**40, mix["warmup_s"],
             cfg["z_dim"], system.annotate(False)).measure()
        engine.completed.clear()
        run.notes["setup_traffic_s"] = time.perf_counter() - t_start
    load = loop(engine, model, mix, run.seed, run.seconds, cfg["z_dim"],
                system.annotate(trace))
    if trace:
        obs.enable()   # the engine's own request timelines: queue wait
    m = engine.metrics
    rows0 = m.samples, m.padded
    run.setup_s = time.perf_counter() - t_start
    prof = tracelib.profiled() if trace else contextlib.nullcontext({})
    with prof as got, gc_watch() as gcs:
        load.measure()
    run.notes.update(gcs)
    if trace:
        obs.disable()
        run.trace = got["trace"]
        run.queue_wait_s = np.array([
            first["dispatch"] - first["admit"]
            for first in map(_first_events, engine.timeline.timelines())
            if "dispatch" in first and "admit" in first])
    load.fill(run)
    run.rows_real, run.rows_padded = m.samples - rows0[0], m.padded - rows0[1]
    run.device["memory_peak_bytes"] = peak_bytes()
    answers = load.answers
    del engine, params, load
    gc.collect()
    t0 = time.perf_counter()
    for name, value in compare(ref, cfg, run.seed, answers).items():
        run.checks[name] = (value, cfg["limits"][name])
    log(f"[check] reference over {len(answers)} requests, "
        f"{sum(len(z) for z, _ in answers)} samples: "
        f"{time.perf_counter() - t0:.2f} s")
    return answers


@contextlib.contextmanager
def gc_watch():
    """Counts Python's garbage collections in the block and the longest,
    so that a stall in the window can be put down to one or cleared."""
    seen = {"gc_collections": 0, "gc_max_ms": 0.0}
    t = [0.0]

    def cb(phase, info):
        if phase == "start":
            t[0] = time.perf_counter()
        else:
            seen["gc_collections"] += 1
            seen["gc_max_ms"] = max(seen["gc_max_ms"],
                                    (time.perf_counter() - t[0]) * 1e3)

    gc.callbacks.append(cb)
    try:
        yield seen
    finally:
        gc.callbacks.remove(cb)


def _first_events(tl) -> dict:
    first = {}
    for e in tl.events:
        first.setdefault(e["event"], e["t"])
    return first


def peak_bytes() -> int:
    """Peak device memory of the fullest chip this process used."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class _Loop:
    """What both loops share: completions, the kept answers, counts."""

    def __init__(self, engine, model, mix, seed, seconds, z_dim, annotate):
        self.engine, self.model, self.mix = engine, model, mix
        self.seed, self.seconds, self.z_dim = seed, seconds, z_dim
        self.ann = annotate
        self.index = {}            # engine rid -> request number
        self.due = {}              # request number -> due time (clock)
        self.done = {}             # request number -> completion time
        self.n_of = {}             # request number -> samples
        self.keep = set()          # request numbers compared afterwards
        self.kept = {}             # request number -> (z, output)
        self.z = {}
        self.failed = set()
        self.dispatches = {}       # bucket -> calls completed in window
        self.t0 = self.t_end = None
        self.max_loop_gap = 0.0

    def _submit(self, i: int, z, due: float) -> None:
        from repro.serve import GenRequest
        from repro.serve.batching import QueueFull

        req = GenRequest(self.model, z)
        self.due[i], self.n_of[i] = due, len(z)
        if i in self.keep:
            self.z[i] = z
        with self.ann("engine.submit"):
            try:
                self.engine.submit(req)
            except QueueFull:
                self.failed.add(i)
                return
        self.index[req.rid] = i

    def _step(self, drain: bool = False) -> list:
        """One engine step; returns the request numbers it completed."""
        eng = self.engine
        with self.ann("engine.step"):
            ran = eng.step(drain=drain)
        if not ran:
            return []
        with self.ann("loadgen.record"):
            out = []
            for r in eng.completed:
                i = self.index.pop(r.rid)
                self.done[i] = r.t_done
                if i in self.keep:
                    self.kept[i] = (self.z.pop(i), r.output)
                r.output = None
                out.append(i)
            if eng.completed and (self.t_end is None
                                  or eng.completed[0].t_done <= self.t_end):
                b = eng.completed[0].bucket
                self.dispatches[b] = self.dispatches.get(b, 0) + 1
            eng.completed.clear()
        return out

    def _drain(self) -> None:
        limit = time.perf_counter() + DRAIN_LIMIT_S
        while self.engine.queued_requests and time.perf_counter() < limit:
            self._step(drain=True)

    @property
    def answers(self) -> list:
        return [self.kept[i] for i in sorted(self.kept)]

    def fill(self, run) -> None:
        n = len(self.due)
        end = max(self.done.values(), default=self.t_end)
        lat = np.empty(n)
        for i in range(n):
            # a request with no answer counts as missing every limit: its
            # latency is the whole wait until the run gave up on it
            lat[i] = self.done.get(i, end) - self.due[i]
        unanswered = n - len(self.done) - len(self.failed)
        run.latencies_s = lat
        run.window_s = self.t_end - self.t0
        run.samples_in_window = sum(self.n_of[i] for i, t in self.done.items()
                                    if t <= self.t_end)
        run.attempted = n
        run.failed = n - len(self.done)
        run.dispatches = dict(self.dispatches)
        run.checks["unanswered"] = (float(unanswered), 0.0)
        run.notes["loop_gap_max_ms"] = self.max_loop_gap * 1e3
        log(f"[loadgen] {n} requests, {sum(self.n_of.values())} samples; "
            f"refused {len(self.failed)}, unanswered {unanswered}; "
            f"longest gap between loop iterations "
            f"{self.max_loop_gap * 1e3:.3f} ms")


class OpenLoop(_Loop):
    """Poisson arrivals on a schedule fixed before the window opens."""

    def __init__(self, *args):
        super().__init__(*args)
        self.sched, self.sizes = loadgen.open_schedule(
            self.mix, self.seed, self.seconds)
        self.zs = loadgen.latents(self.seed, self.sizes, self.z_dim)
        self.keep = set(loadgen.open_sample(
            self.mix, self.seed, self.sizes).tolist())
        self.late = np.zeros(len(self.sched))
        self.gap_at = 0.0

    def measure(self) -> None:
        eng, sched, n = self.engine, self.sched, len(self.sched)
        wait = eng.policy.max_wait_s
        clock = time.perf_counter
        with self.ann("bench.window"):
            self.t0 = t0 = clock()
            last, i = t0, 0
            while i < n or eng.queued_requests:
                now = clock()
                if now - last > self.max_loop_gap:
                    self.max_loop_gap, self.gap_at = now - last, last - t0
                last = now
                while i < n and sched[i] <= now - t0:
                    self._submit(i, self.zs[i], t0 + sched[i])
                    self.late[i] = clock() - t0 - sched[i]
                    i += 1
                if self._step():
                    continue
                if i < n:
                    nap = sched[i] - (clock() - t0)
                    if eng.queued_requests:
                        nap = min(nap, wait)
                    if nap > 0:
                        with self.ann("loadgen.wait"):
                            time.sleep(min(nap, 1e-3))
                else:
                    self._step(drain=True)
                if now - t0 > self.seconds + DRAIN_LIMIT_S:
                    break
            self.t_end = t0 + self.seconds
        self._drain()

    def fill(self, run) -> None:
        super().fill(run)
        run.notes["loop_gap_at_s"] = self.gap_at
        run.lateness_s = self.late
        summary = loadgen.lateness_summary(self.late)
        run.notes.update({f"late_{k}": v for k, v in summary.items()})
        log("[loadgen] lateness " + ", ".join(
            f"{k} {v:.3f}" for k, v in summary.items()))


class ClosedLoop(_Loop):
    """``clients`` callers, each with one request outstanding."""

    def __init__(self, *args):
        super().__init__(*args)
        self.gen = loadgen.ClosedLatents(self.seed, self.mix["size"],
                                         self.z_dim)
        self.keep = loadgen.closed_sample(self.mix, self.seed)
        self.sent = 0

    def _send(self) -> None:
        self._submit(self.sent, self.gen.next(), time.perf_counter())
        self.sent += 1

    def measure(self) -> None:
        clock = time.perf_counter
        with self.ann("bench.window"):
            self.t0 = t0 = clock()
            for _ in range(self.mix["clients"]):
                self._send()
            last = t0
            # the window closes at the first reply after ``seconds``, so
            # it holds whole dispatches only
            while True:
                now = clock()
                self.max_loop_gap = max(self.max_loop_gap, now - last)
                last = now
                done = self._step()
                if clock() - t0 >= self.seconds and done:
                    self.t_end = clock()
                    break
                for _ in done:
                    self._send()
                if now - t0 > self.seconds + DRAIN_LIMIT_S:
                    self.t_end = clock()
                    break
        self._drain()


# the controls: the reference put in the program's place, in bfloat16
# throughout, or with bfloat16 weights and activations and the last layer
# summed and finished in float32 (a bfloat16 model with float32 outputs)
CONTROLS = {"bfloat16": None, "bfloat16_f32_out": "float32"}


def compare(ref, cfg: dict, seed: int, answers, control=None) -> dict:
    """The readings of the served answers against the reference's outputs
    for the same latents:

    * ``image_rel_rms`` — the worst request's relative RMS gap to the
      reference at ``highest`` precision;
    * ``image_rel_rms_default`` — the same against the reference at the
      precision the configuration states (``matmul_precision``; at
      ``default`` each product of bfloat16-rounded inputs, summed in
      float32). Activations kept in bfloat16 between layers round once
      more per layer;
    * ``bf16_exact_share`` — the share of served values that bfloat16
      holds exactly. Float32 results land on bfloat16's grid about once
      in 2**16; results stored in bfloat16 always do. It sees only the
      type of the output, not the precision it was computed in.

    With ``control`` (a key of :data:`CONTROLS`) the reference itself, in
    bfloat16, answers in the program's place."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    failed = {"image_rel_rms": float("inf"),
              "image_rel_rms_default": float("inf"), "bf16_exact_share": 1.0}
    if not answers:
        return failed
    params = system.generator_weights(ref, cfg, seed)
    fn = jax.jit(lambda p, z: (
        ref.generate(p, cfg, z),
        ref.generate(p, cfg, z,
                     precision=lax.Precision(cfg["matmul_precision"]))))
    if control is not None:
        out_dtype = CONTROLS[control]
        low_params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)
        low = jax.jit(lambda p, z: ref.generate(
            p, cfg, z.astype(jnp.bfloat16), out_dtype=out_dtype))

    @jax.jit
    def gaps(got, want, want_default):
        bits = jax.lax.bitcast_convert_type(got, jnp.uint32)
        return (jnp.sum(jnp.square(got - want)),
                jnp.sum(jnp.square(got - want_default)),
                jnp.sum(jnp.square(want)),
                jnp.sum((bits & 0xFFFF) == 0), jnp.all(jnp.isfinite(got)))

    worst = worst_default = 0.0
    exact = total = 0
    for z, out in answers:
        n = len(z)
        zp = np.zeros((REFERENCE_ROWS, z.shape[1]), np.float32)
        zp[:n] = z
        want, want_default = fn(params, zp)
        got = low(low_params, zp) if control is not None else np.asarray(out)
        if got.shape[1:] != want.shape[1:] or got.shape[0] < n:
            return failed
        got = jnp.asarray(got)[:n].astype(jnp.float32)
        diff2, diff2_default, ref2, on_grid, finite = gaps(
            got, want[:n], want_default[:n])
        if not bool(finite):
            return failed
        ref2 = max(float(ref2), 1e-30)
        worst = max(worst, float(np.sqrt(float(diff2) / ref2)))
        worst_default = max(worst_default,
                            float(np.sqrt(float(diff2_default) / ref2)))
        exact += int(on_grid)
        total += got.size
    return {"image_rel_rms": worst, "image_rel_rms_default": worst_default,
            "bf16_exact_share": exact / total}
