# One function per paper table, plus the transpose-conv perf-trajectory
# artifact (BENCH_transpose_conv.json). Prints CSV blocks per table.
#
#   python -m benchmarks.run            # full sweep (all tables + artifact)
#   python -m benchmarks.run --quick    # CI smoke: artifact only, <60 s
#
# Quick mode smoke-runs forward, backward, AND full-train-step timings
# (transpose_conv_bench --quick --check) and fails on the Pallas gates
# (fused >= per-phase, pallas bwd >= lax bwd), then the serving benchmark
# (serving_bench --quick --check), failing unless the bucketed engine beats
# sequential per-request dispatch by the floor factor with zero steady-state
# recompiles AND both serving chaos runs pass (kill-one and hang-one of two
# replicas mid-trace: recovery on the survivor, request conservation,
# bitwise-equal retried outputs, zero per-replica retraces), then the
# training benchmark (training_bench --quick --check),
# a crash-resume smoke that fails unless a mid-run kill relaunches from the
# newest checkpoint onto a bit-exact loss trajectory. Full mode additionally
# runs table4_gans, which merges its train rows into the same artifact (the
# bench preserves the table4_train section when it rewrites the file).
from __future__ import annotations

import argparse
import time

from repro.compile_cache import use_compile_cache


def main(argv=None) -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--quick", action="store_true",
        help="smoke mode for CI: quick transpose-conv benchmark only",
    )
    args = ap.parse_args(argv)

    from benchmarks import serving_bench, training_bench, transpose_conv_bench

    if args.quick:
        t0 = time.time()
        print("\n===== transpose_conv_bench (quick) =====")
        transpose_conv_bench.main(["--quick", "--check"])
        print(f"[transpose_conv_bench] {time.time() - t0:.1f}s")
        t0 = time.time()
        print("\n===== serving_bench (quick) =====")
        serving_bench.main(["--quick", "--check"])
        print(f"[serving_bench] {time.time() - t0:.1f}s")
        t0 = time.time()
        print("\n===== training_bench (quick) =====")
        training_bench.main(["--quick", "--check"])
        print(f"[training_bench] {time.time() - t0:.1f}s")
        return

    from benchmarks import (
        flops_memory,
        roofline_table,
        table2_flowers,
        table3_coco_pascal,
        table4_gans,
    )

    for name, mod in [
        ("table2_flowers", table2_flowers),
        ("table3_coco_pascal", table3_coco_pascal),
        ("table4_gans", table4_gans),
        ("flops_memory", flops_memory),
        ("roofline_table", roofline_table),
    ]:
        t0 = time.time()
        print(f"\n===== {name} =====")
        mod.main()
        print(f"[{name}] {time.time() - t0:.1f}s")

    t0 = time.time()
    print("\n===== transpose_conv_bench =====")
    transpose_conv_bench.main(["--check"])
    print(f"[transpose_conv_bench] {time.time() - t0:.1f}s")

    t0 = time.time()
    print("\n===== serving_bench =====")
    serving_bench.main(["--check"])
    print(f"[serving_bench] {time.time() - t0:.1f}s")

    t0 = time.time()
    print("\n===== training_bench =====")
    training_bench.main(["--check"])
    print(f"[training_bench] {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
