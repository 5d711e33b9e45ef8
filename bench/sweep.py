"""Find the knee of an open-loop serving cell: one run per rate.

    python3 bench/sweep.py --workload <cell> --rates 500,1000,2000 \
        --seconds 10 --seed <n>

For each rate the cell's mix runs at that rate instead of its own; one
JSON line per rate gives what was offered and served, the 95th percentile
latency, how late the generator ran, and the backlog left at the close.
A cell's rate is fixed in its mix; this only finds where to fix it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as benchrun  # noqa: E402
from harness import loadgen, registry, serve  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    base = registry.cell(registry.load_benchmark(), args.workload)
    benchrun.setup_caches()
    try:
        device = benchrun.device_info(base.chips)
    except benchrun.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    p95_ms = registry.metric_reader("latency_p95_ms")
    for rate in (float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(
            base, traffic={**base.traffic, "rate_rps": rate})
        run = benchrun.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                           peaks=benchrun.peaks(device["kind"]),
                           device=dict(device))
        serve.run_serving(run, time.perf_counter(), False)
        print(json.dumps({
            "rate_rps": rate,
            "offered_samples_per_s": float(loadgen.open_schedule(
                cell.traffic, args.seed, args.seconds)[1].sum())
            / args.seconds,
            "samples_per_s": run.samples_in_window / run.window_s,
            "latency_p95_ms": p95_ms(run),
            "late_p99_ms": run.notes.get("late_p99_ms"),
            "loop_gap_max_ms": run.notes.get("loop_gap_max_ms"),
            "failed": run.failed, "correct": run.correct}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
