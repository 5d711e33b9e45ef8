"""Serving-throughput benchmark: bucketed dynamic batching vs sequential
per-request generation on the same request trace.

The `serving` section this writes into BENCH_transpose_conv.json answers
the deployment question the engine exists for: given a stream of small
mixed-size generation requests, how much throughput does bucket-batched
dispatch over precompiled TconvPlans buy over serving each request
individually (one warmed, plan-compiled jit call per request — the
strongest sequential baseline the repo has)?

Both sides run the identical trace and the identical executables
(whole-generator plans, fused epilogues); the only difference is batch
formation. Under ``--check`` the section gates two invariants:

* bucketed engine throughput >= SERVING_SPEEDUP_FLOOR x sequential;
* zero steady-state recompiles (the engine's trace-time counter must not
  move after warmup across the whole timed run).

The ``chaos`` subsection is the serving-resilience twin of the trainer's
fault-injection smoke: the same trace runs through a two-replica
:class:`~repro.serve.supervisor.ReplicaSupervisor` with a deterministic
fault injected mid-trace — a **kill** run (one replica crashes and stays
down) and a **hang** run (one dispatch stalls past its timeout). Under
``--check`` each run gates the resilience contract:

* every request completes on the surviving replica (requeue happened,
  nothing hung, nothing lost: the conservation ledger balances);
* retried outputs are bitwise-equal to unbatched ``generator_apply`` —
  a rerouted batch is indistinguishable from a clean one;
* per-replica steady-state recompiles stay zero under faults (a retried
  bucket re-runs a warmed executable, never a fresh trace).

The ``observability`` subsection (docs/OBSERVABILITY.md) gates the obs
layer's two contracts on the same trace: **disabled = free** (a tracer-off
run records zero spans/events/counters and its wall stays within
``OBS_OVERHEAD_CEILING`` of the serving run above) and **enabled =
complete** (every request in the traced run has a complete timeline that
reconciles against the conservation ledger; the Chrome-trace export is
structurally valid; the Prometheus snapshot parses; a recorder-attached
chaos kill writes a flight dump; an in-memory autotune race writes one
audit entry per direction). The Chrome trace and flight dump are written
next to the BENCH json (``BENCH_obs_trace.json`` / ``BENCH_obs_flight.json``)
and uploaded as CI artifacts.

Quick mode (CI) uses a reduced DCGAN and a short trace; full mode serves
two zoo models through one engine at longer traces.
"""
from __future__ import annotations

import argparse
import json
import shutil
import time
from pathlib import Path

import numpy as np

from repro.compile_cache import use_compile_cache

SERVING_SPEEDUP_FLOOR = 1.3
OBS_OVERHEAD_CEILING = 1.03   # tracer-off wall vs the serving run's wall
OBS_WALL_SLACK_S = 0.01       # absolute jitter allowance on tiny walls


def make_trace(models, z_dim, n_requests, *, seed=0):
    """Deterministic Poisson-style trace: request sizes drawn from a
    small-skewed distribution (most requests want 1-2 samples), models
    round-robined. Returns (model, z) pairs in arrival order."""
    rng = np.random.default_rng(seed)
    sizes = rng.choice([1, 1, 1, 2], size=n_requests)
    return [
        (models[i % len(models)],
         rng.standard_normal((int(n), z_dim)).astype(np.float32))
        for i, n in enumerate(sizes)
    ]


def bench_serving(*, quick: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.models import gan
    from repro.serve import BucketPolicy, GanEngine, GenRequest
    from repro.serve.gan_engine import sequential_executables

    names = ["dcgan"] if quick else ["dcgan", "gpgan"]
    cfgs = {n: gan.reduced_config(gan.GAN_ZOO[n], scale=64) for n in names}
    n_requests = 48 if quick else 160
    repeats = 2 if quick else 3

    policy = BucketPolicy(
        buckets=(1, 2, 4, 8, 16), max_wait_s=0.05, max_queue=4 * n_requests
    )
    engine = GanEngine(policy)
    params = {}
    for i, (name, cfg) in enumerate(cfgs.items()):
        params[name] = gan.generator_init(jax.random.key(i), cfg)
        engine.register(cfg, params[name], name=name)
    engine.warmup()

    trace = make_trace(names, next(iter(cfgs.values())).z_dim, n_requests)

    # ---- bucketed engine: burst-submit the trace, drain, best of repeats
    recompiles_before = engine.metrics.recompiles
    engine_s = float("inf")
    for _ in range(repeats):
        reqs = [GenRequest(m, z) for m, z in trace]
        t0 = time.perf_counter()
        engine.serve(reqs)
        engine_s = min(engine_s, time.perf_counter() - t0)
    recompiles_steady = engine.metrics.recompiles - recompiles_before

    # ---- sequential baseline: one warmed plan-compiled call per request,
    # at each request's exact size (no padding — the baseline's advantage)
    seq_fns = {}
    for name, cfg in cfgs.items():
        sizes = sorted({z.shape[0] for m, z in trace if m == name})
        for n, fn in sequential_executables(cfg, params[name], sizes).items():
            seq_fns[name, n] = fn

    sequential_s = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for m, z in trace:
            jax.block_until_ready(
                seq_fns[m, z.shape[0]](params[m], jnp.asarray(z))
            )
        sequential_s = min(sequential_s, time.perf_counter() - t0)

    n_samples = sum(z.shape[0] for _, z in trace)
    m = engine.metrics
    return {
        "backend": jax.default_backend(),
        "quick": quick,
        "models": names,
        "buckets": list(policy.buckets),
        "n_requests": n_requests,
        "n_samples": n_samples,
        "repeats": repeats,
        "engine_s": engine_s,
        "sequential_s": sequential_s,
        "speedup": sequential_s / engine_s,
        "samples_per_s": n_samples / engine_s,
        "pad_waste": m.pad_waste,
        "warmup_recompiles": engine.warmup_recompiles,
        "recompiles_steady": recompiles_steady,
        "latency_s": m.latency_percentiles(),
        "conservation": engine.conservation(),
        "per_model": m.summary()["per_model"],
    }


def _chaos_run(fault: str, *, quick: bool, recorder=None) -> dict:
    """One supervised two-replica run of the quick trace with a
    deterministic fault injected mid-trace. ``fault`` is ``"kill"`` (r0
    crashes at its 3rd dispatch and stays down) or ``"hang"`` (r0's 3rd
    dispatch stalls past the dispatch timeout). Returns the resilience
    counters plus the three gate verdicts. ``recorder`` (an obs
    :class:`~repro.obs.flight_recorder.FlightRecorder`) rides on the
    supervisor and dumps on the injected replica's DEAD transition."""
    import jax
    import jax.numpy as jnp

    from repro.models import gan
    from repro.serve import BucketPolicy, GenRequest
    from repro.serve.fault_injection import (
        ServeFaultInjector,
        ServeFaultPlan,
    )
    from repro.serve.replica import Replica
    from repro.serve.supervisor import ReplicaSupervisor

    cfg = gan.reduced_config(gan.GAN_ZOO["dcgan"], scale=64)
    params = gan.generator_init(jax.random.key(0), cfg)
    n_requests = 24 if quick else 64

    if fault == "kill":
        plan = ServeFaultPlan(crash_at=(("r0", 3),))
        timeout_s = 5.0            # generous: the kill run gates routing
    else:
        plan = ServeFaultPlan(hang_at=(("r0", 3, 1.0),))
        timeout_s = 0.2            # tight: the hang must overshoot it

    inj = ServeFaultInjector(plan)
    replicas = [Replica("r0", dispatch_hook=inj.hook),
                Replica("r1", dispatch_hook=inj.hook)]
    sup = ReplicaSupervisor(
        replicas,
        BucketPolicy(buckets=(1, 2, 4), max_wait_s=0.05,
                     max_queue=4 * n_requests),
        retry_budget=4, timeout_s=timeout_s, recorder=recorder,
        # With a recorder riding, make the SUSPECT probe due immediately:
        # healthy peers absorb the short quick trace, so without this the
        # killed replica would linger SUSPECT past the end of the run and
        # the DEAD-transition flight dump the gate checks for never fires.
        probe_backoff_s=0.0 if recorder is not None else 0.05,
    )
    sup.register(cfg, params)
    sup.warmup()
    warm = dict(sup.replica_recompiles)

    trace = make_trace(["dcgan"], cfg.z_dim, n_requests, seed=7)
    reqs = [GenRequest(m, z) for m, z in trace]
    t0 = time.perf_counter()
    sup.serve(reqs)
    wall_s = time.perf_counter() - t0

    # gate 1: recovered — everything done, ledger balanced, batch requeued
    ledger = sup.conservation()
    recovered = (
        all(r.done for r in reqs)
        and bool(ledger["ok"])
        and sup.metrics.requeues >= 1
        and any(e[0] == fault.replace("kill", "crash") for e in inj.fired)
    )
    # gate 2: retried outputs bitwise-equal to unbatched generator_apply
    retried = [r for r in reqs if r.retries > 0]
    sample = retried + [r for r in reqs if r.retries == 0][:4]
    bitwise_equal = all(
        r.done and np.array_equal(
            np.asarray(r.output),
            np.asarray(gan.generator_apply(params, cfg, jnp.asarray(r.z))),
        )
        for r in sample
    )
    # gate 3: no replica retraced under the fault, no inline compile
    steady = {rid: n - warm[rid]
              for rid, n in sup.replica_recompiles.items()}
    zero_retraces = (all(v == 0 for v in steady.values())
                     and sup.metrics.recompiles == 0)

    m = sup.metrics
    return {
        "fault": fault,
        "n_requests": n_requests,
        "wall_s": wall_s,
        "done": m.requests,
        "failed": m.failed,
        "retries": m.retries,
        "requeues": m.requeues,
        "timeouts": m.timeouts,
        "nonfinite": m.nonfinite,
        "probes": m.probes,
        "degraded_batches": m.degraded_batches,
        "replica_transitions": dict(m.transition_counts),
        "replica_states": sup.replica_states(),
        "retried_requests": len(retried),
        "steady_recompiles": steady,
        "conservation_ok": bool(ledger["ok"]),
        "recovered": bool(recovered),
        "bitwise_equal": bool(bitwise_equal),
        "zero_retraces": bool(zero_retraces),
    }


def bench_chaos(*, quick: bool) -> dict:
    """The serving chaos smoke: kill-one and hang-one runs (see
    :func:`_chaos_run`) on a two-replica supervisor."""
    return {f: _chaos_run(f, quick=quick) for f in ("kill", "hang")}


def bench_observability(*, quick: bool, baseline_engine_s: float,
                        out_dir: Path) -> dict:
    """The obs-layer gates (see module docstring): disabled fast path,
    traced-run timeline completeness + exporter validity, a
    recorder-attached chaos kill's flight dump, and the autotune audit
    trail. Writes ``BENCH_obs_trace.json`` and ``BENCH_obs_flight.json``
    into ``out_dir``."""
    import tempfile

    import jax

    from repro.kernels.autotune import tune_layer
    from repro.models import gan
    from repro.obs import (
        FlightRecorder,
        chrome_trace,
        parse_prometheus_text,
        prometheus_text,
    )
    from repro.obs import trace as obs
    from repro.obs.audit import AuditTrail, set_trail
    from repro.obs.export import validate_chrome_trace, write_chrome_trace
    from repro.serve import BucketPolicy, GanEngine, GenRequest

    names = ["dcgan"] if quick else ["dcgan", "gpgan"]
    cfgs = {n: gan.reduced_config(gan.GAN_ZOO[n], scale=64) for n in names}
    n_requests = 48 if quick else 160
    repeats = 2 if quick else 3

    def build_engine():
        policy = BucketPolicy(buckets=(1, 2, 4, 8, 16), max_wait_s=0.05,
                              max_queue=4 * n_requests)
        eng = GanEngine(policy)
        for i, (name, cfg) in enumerate(cfgs.items()):
            eng.register(cfg, gan.generator_init(jax.random.key(i), cfg),
                         name=name)
        eng.warmup()
        return eng

    trace = make_trace(names, next(iter(cfgs.values())).z_dim, n_requests)

    # ---- disabled fast path: same trace, tracer off, isolated registry —
    # the wall must match the serving run above and NOTHING may be recorded
    probe_tracer = obs.Tracer()
    prev_tracer = obs.set_tracer(probe_tracer)
    was_enabled = obs.enabled()
    obs.disable()
    try:
        engine = build_engine()
        disabled_s = float("inf")
        for _ in range(repeats):
            reqs = [GenRequest(m, z) for m, z in trace]
            t0 = time.perf_counter()
            engine.serve(reqs)
            disabled_s = min(disabled_s, time.perf_counter() - t0)
        zero_events = (
            len(probe_tracer.spans) == 0
            and len(probe_tracer.instants) == 0
            and not probe_tracer.counters
            and not probe_tracer.observations
            and len(engine.timeline) == 0
        )

        # ---- enabled run: full span tree + per-request timelines
        tracer = obs.Tracer()
        obs.set_tracer(tracer)
        obs.enable()
        engine2 = build_engine()
        reqs2 = [GenRequest(m, z) for m, z in trace]
        t0 = time.perf_counter()
        engine2.serve(reqs2)
        enabled_s = time.perf_counter() - t0
        obs.disable()

        timelines = engine2.timeline.timelines()
        timelines_complete = (
            len(timelines) == n_requests
            and all(tl.complete for tl in timelines)
            and not engine2.timeline.incomplete()
        )
        reconcile = engine2.timeline.reconcile(
            engine2.metrics.conservation()
        )
        engine2.metrics.publish(tracer)
        trace_path = out_dir / "BENCH_obs_trace.json"
        write_chrome_trace(tracer, trace_path, timeline=engine2.timeline)
        trace_problems = validate_chrome_trace(
            json.loads(trace_path.read_text())
        )
        try:
            prom = parse_prometheus_text(prometheus_text(tracer))
            prom_valid = prom["metrics"].get("serve_admitted_total") is not None
        except ValueError:
            prom_valid = False

        # ---- chaos kill with a recorder attached: the DEAD transition
        # must leave a post-mortem artifact
        with tempfile.TemporaryDirectory() as td:
            recorder = FlightRecorder(dump_dir=td)
            obs.enable()
            chaos = _chaos_run("kill", quick=True, recorder=recorder)
            obs.disable()
            flight_path = out_dir / "BENCH_obs_flight.json"
            if recorder.dumps:
                shutil.copy(recorder.dumps[0], flight_path)
            flight = {
                "dumps": len(recorder.dumps),
                "dump_written": bool(recorder.dumps)
                and flight_path.exists(),
                "dump_trigger": (FlightRecorder.load(flight_path)["trigger"]
                                 if recorder.dumps and flight_path.exists()
                                 else None),
            }

        # ---- autotune audit: an in-memory race records one decision per
        # tuned direction (lax-only candidates: wall-clockable on any
        # backend; persist=False keeps the tier-1 cache untouched)
        trail = AuditTrail(path=None)
        prev_trail = set_trail(trail)
        try:
            tune_layer(1, 4, 4, 2, 3, 1,
                       methods=("conventional", "unified_reshape"),
                       repeats=1, warmup=0, persist=False)
        finally:
            set_trail(prev_trail)
        audit_ok = (
            len(trail.records) == 1
            and trail.records[0]["direction"] == "fwd"
            and trail.records[0]["winner"] is not None
            and len(trail.records[0]["candidates"]) == 2
        )
    finally:
        obs.set_tracer(prev_tracer)
        if was_enabled:
            obs.enable()
        else:
            obs.disable()

    return {
        "baseline_engine_s": baseline_engine_s,
        "disabled_s": disabled_s,
        "enabled_s": enabled_s,
        "overhead_ratio_disabled": disabled_s / baseline_engine_s,
        "overhead_ratio_enabled": enabled_s / disabled_s,
        "zero_events_when_disabled": bool(zero_events),
        "disabled_within_ceiling": bool(
            disabled_s
            <= OBS_OVERHEAD_CEILING * baseline_engine_s + OBS_WALL_SLACK_S
        ),
        "spans_recorded": len(tracer.spans),
        "span_names": tracer.span_names(),
        "timelines": len(timelines),
        "timelines_complete": bool(timelines_complete),
        "reconcile_ok": bool(reconcile["ok"]),
        "trace_artifact": trace_path.name,
        "trace_valid": not trace_problems,
        "prometheus_valid": bool(prom_valid),
        "flight": flight,
        "chaos_recovered": bool(chaos["recovered"]),
        "audit_ok": bool(audit_ok),
    }


def check(section: dict) -> list[str]:
    """The acceptance gates: bucketed serving must beat sequential dispatch
    by the floor factor with zero steady-state recompiles, and both chaos
    runs must recover (requeue to the survivor, conserve every request,
    bitwise-equal retried outputs, zero per-replica retraces)."""
    bad = []
    if section["speedup"] < SERVING_SPEEDUP_FLOOR:
        bad.append(
            f"serving: speedup={section['speedup']:.3f} < "
            f"{SERVING_SPEEDUP_FLOOR}x sequential "
            f"(engine {section['engine_s']:.4f}s vs "
            f"sequential {section['sequential_s']:.4f}s)"
        )
    if section["recompiles_steady"] != 0:
        bad.append(
            f"serving: {section['recompiles_steady']} steady-state "
            "recompiles after warmup (must be 0)"
        )
    for fault, run in section.get("chaos", {}).items():
        for gate in ("recovered", "conservation_ok", "bitwise_equal",
                     "zero_retraces"):
            if not run[gate]:
                bad.append(f"serving chaos [{fault}]: {gate} failed "
                           f"({run})")
    ob = section.get("observability")
    if ob is not None:
        if not ob["zero_events_when_disabled"]:
            bad.append("obs: tracer-off run recorded events "
                       "(disabled path must record nothing)")
        if not ob["disabled_within_ceiling"]:
            bad.append(
                f"obs: tracer-off wall {ob['disabled_s']:.4f}s exceeds "
                f"{OBS_OVERHEAD_CEILING}x serving baseline "
                f"{ob['baseline_engine_s']:.4f}s"
            )
        if not ob["timelines_complete"]:
            bad.append(
                f"obs: {ob['timelines']} timelines for the traced run are "
                "not all complete (admit + terminal present)"
            )
        if not ob["reconcile_ok"]:
            bad.append("obs: timeline terminal counts do not reconcile "
                       "with the conservation ledger")
        if not ob["trace_valid"]:
            bad.append("obs: Chrome-trace artifact failed validation")
        if not ob["prometheus_valid"]:
            bad.append("obs: Prometheus snapshot failed to parse")
        if not ob["flight"]["dump_written"]:
            bad.append("obs: chaos kill run left no flight-recorder dump")
        if not ob["audit_ok"]:
            bad.append("obs: autotune race recorded no audit decision")
    return bad


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smoke subset: dcgan only, short trace")
    ap.add_argument("--out", default="BENCH_transpose_conv.json")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless engine >= "
                         f"{SERVING_SPEEDUP_FLOOR}x sequential with zero "
                         "steady-state recompiles")
    args = ap.parse_args(argv)

    section = bench_serving(quick=args.quick)
    section["chaos"] = bench_chaos(quick=args.quick)
    out_path = Path(args.out)
    section["observability"] = bench_observability(
        quick=args.quick, baseline_engine_s=section["engine_s"],
        out_dir=out_path.resolve().parent,
    )

    merged = {}
    if out_path.exists():   # merge into the shared perf artifact
        try:
            merged = json.loads(out_path.read_text())
            if not isinstance(merged, dict):
                merged = {}
        except (json.JSONDecodeError, OSError):
            merged = {}
    merged["serving"] = section
    out_path.write_text(json.dumps(merged, indent=1, sort_keys=True))

    lat = section["latency_s"]
    print(f"# serving ({'quick' if args.quick else 'full'}, "
          f"backend={section['backend']}): "
          f"{section['n_requests']} reqs / {section['n_samples']} samples, "
          f"models={','.join(section['models'])}")
    print(f"engine {section['engine_s']:.4f}s "
          f"({section['samples_per_s']:.0f} samples/s) vs sequential "
          f"{section['sequential_s']:.4f}s -> x{section['speedup']:.2f}; "
          f"pad waste {section['pad_waste'] * 100:.1f}%, "
          f"recompiles steady {section['recompiles_steady']} "
          f"(warmup {section['warmup_recompiles']}); "
          f"latency ms p50 {lat['p50'] * 1e3:.1f} p95 {lat['p95'] * 1e3:.1f} "
          f"p99 {lat['p99'] * 1e3:.1f}")
    for fault, run in section["chaos"].items():
        print(f"chaos [{fault}]: {run['done']}/{run['n_requests']} done in "
              f"{run['wall_s']:.2f}s; {run['retries']} retries, "
              f"{run['requeues']} requeues, {run['timeouts']} timeouts, "
              f"{run['probes']} probes; transitions "
              f"{run['replica_transitions']}; "
              f"recovered={run['recovered']} "
              f"bitwise={run['bitwise_equal']} "
              f"zero_retraces={run['zero_retraces']}")
    ob = section["observability"]
    print(f"obs: disabled {ob['disabled_s']:.4f}s "
          f"(x{ob['overhead_ratio_disabled']:.3f} of baseline, "
          f"zero_events={ob['zero_events_when_disabled']}), enabled "
          f"{ob['enabled_s']:.4f}s (x{ob['overhead_ratio_enabled']:.2f}); "
          f"{ob['spans_recorded']} spans, {ob['timelines']} timelines "
          f"(complete={ob['timelines_complete']}, "
          f"reconcile={ob['reconcile_ok']}); trace_valid={ob['trace_valid']} "
          f"prom_valid={ob['prometheus_valid']} "
          f"flight_dump={ob['flight']['dump_written']} "
          f"audit={ob['audit_ok']}")

    bad = check(section)
    if bad:
        print("PERF REGRESSION on:", "; ".join(bad))
        if args.check:
            raise SystemExit(1)
    elif args.check:
        print(f"# check ok: bucketed engine >= {SERVING_SPEEDUP_FLOOR}x "
              "sequential per-request dispatch, zero steady-state "
              "recompiles; chaos kill+hang runs recovered with "
              "conservation, bitwise-equal retries, zero retraces; obs "
              "disabled-path free + complete timelines + valid exports + "
              "flight dump + audit trail")


if __name__ == "__main__":
    main()
