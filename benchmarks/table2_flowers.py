"""Paper Table 2: speedup + memory savings on the Flower dataset groups.

The paper converts every image to 224x224x3 and sweeps kernels 3x3..5x5,
reporting conventional vs proposed (unified) computation time and the
memory savings from never materializing the upsampled map. We reproduce the
same workload on synthetic 224x224x3 images (dataset content doesn't affect
the operator's arithmetic) with the per-group sample counts of Table 1,
timing per-image and deriving dataset totals.
"""
from __future__ import annotations

import jax

from repro.core import memory_savings_bytes, transpose_conv2d
from benchmarks.common import csv_row, rand_image, rand_kernel, time_fn
from repro.compile_cache import use_compile_cache

GROUPS = {
    "sunflower": 734, "tulip": 984, "daisy": 769, "rose": 784,
    "dandelion": 1052,
}
KERNELS = [5, 4, 3]
COUT = 3


def run(batch=4, groups=None, padding=2):
    x = rand_image(0, 224, 3, batch)
    rows = []
    for n in KERNELS:
        k = rand_kernel(n, n, 3, COUT)
        fns = {
            m: jax.jit(
                lambda x, k, m=m: transpose_conv2d(x, k, padding, method=m)
            )
            for m in ("conventional", "unified")
        }
        t_conv = time_fn(fns["conventional"], x, k) / batch
        t_uni = time_fn(fns["unified"], x, k) / batch
        mem = memory_savings_bytes(224, 3, 4, padding)
        for g, count in (groups or GROUPS).items():
            rows.append({
                "group": g, "kernel": n,
                "conv_s_dataset": t_conv * count,
                "prop_s_dataset": t_uni * count,
                "speedup": t_conv / t_uni,
                "mem_savings_MB": mem / 1e6,
            })
    return rows


def main():
    use_compile_cache()
    print("# Table 2 — Flower dataset (CPU, per-dataset seconds)")
    print("group,kernel,conv_s,prop_s,speedup,mem_savings_MB")
    for r in run():
        print(f"{r['group']},{r['kernel']}x{r['kernel']}x3,"
              f"{r['conv_s_dataset']:.3f},{r['prop_s_dataset']:.3f},"
              f"{r['speedup']:.3f},{r['mem_savings_MB']:.4f}")
    csv_row("table2_done", 0.0, "see rows above")


if __name__ == "__main__":
    main()
