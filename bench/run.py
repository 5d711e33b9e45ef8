"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
the names in ``BENCHMARK.json``. The run needs a TPU with at least the
chips the cell asks for; without one it exits 2 and prints no result.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. The last line of standard output is one JSON object; the numbers
the correctness check compared come last on standard error and last in
that object, each beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import registry  # noqa: E402
from harness import trace as tracelib  # noqa: E402
from harness.peaks import peaks  # noqa: E402
from harness.record import Run  # noqa: E402

# JAX's persistent compilation cache, at a fixed path inside the checkout
# unless JAX_COMPILATION_CACHE_DIR names one
CACHE_DIR = registry.ROOT / ".jax_cache"
# the autotuner's cache, also inside the checkout: a cold plan on a fresh
# checkout, the same plan on every later run there
AUTOTUNE_CACHE = registry.ROOT / ".bench_state" / "autotune.json"


class NoChip(RuntimeError):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_caches() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program, however quick to compile, so that only a checkout's
    # first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    AUTOTUNE_CACHE.parent.mkdir(exist_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(AUTOTUNE_CACHE)


def device_info(chips: int) -> dict:
    """The devices JAX found; raises :class:`NoChip` without enough TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def execute(cell, seed: int, seconds: float, trace: bool, device: dict,
            t_start: float) -> Run:
    """Everything after the look for a chip: set up, measure, check."""
    from harness.serve import run_serving
    from harness.train import run_training

    run = Run(cell=cell, seed=seed, seconds=seconds,
              peaks=peaks(device["kind"]), device=dict(device))
    kind = cell.traffic["kind"]
    if kind in ("open", "closed"):
        run_serving(run, t_start, trace)
    elif kind == "train":
        run_training(run, t_start, trace)
    else:
        raise registry.UnknownName(f"no harness for traffic kind {kind!r}")
    return run


def result(run: Run, trace: bool) -> dict:
    entries = run.cell.per_layer if trace else run.cell.end_to_end
    out = {"correct": run.correct, "attempted": int(run.attempted),
           "failed": int(run.failed),
           "metrics": registry.read_metrics(entries, run),
           "device": dict(run.device)}
    if trace and run.trace is not None:
        tr = run.trace
        out["device"]["busy_s"] = tracelib.busy_s(tr)
        out["device"]["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": tracelib.op_seconds(tr),
                            "idle_gaps": tracelib.idle_gaps(tr)}
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, (v, lim) in run.checks.items()}
    return out


def report(run: Run) -> None:
    """Earlier lines: what explains a slow or stalled run. Last lines of
    standard error: each number compared beside its limit."""
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    print(f"[host] peak resident memory {rss_mb:.1f} MB; setup "
          f"{run.setup_s:.3f} s; window {run.window_s:.3f} s; "
          + "; ".join(f"{k} {v:.6g}" for k, v in run.notes.items()),
          file=sys.stderr)
    for name, (v, lim) in run.checks.items():
        print(f"check {name}: {v!r} (limit {lim!r})", file=sys.stderr)


def main(argv=None) -> int:
    args = parse(argv)
    bench = registry.load_benchmark()
    cell = registry.cell(bench, args.workload)
    try:
        device = device_info(cell.chips)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    setup_caches()
    run = execute(cell, args.seed, args.seconds, bool(args.trace), device,
                  T_START)
    out = result(run, bool(args.trace))
    report(run)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
