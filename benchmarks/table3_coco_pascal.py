"""Paper Table 3: MSCOCO 2017 / PASCAL VOC 2012 speedups (224x224x3,
kernels 3..5) — same operator workload as Table 2 with the larger dataset
sample counts."""
from __future__ import annotations

from benchmarks.table2_flowers import run
from repro.compile_cache import use_compile_cache


DATASETS = {
    "mscoco2017_10pct": 11_828,
    "pascal_voc2012_classification": 17_125,
    "pascal_voc2012_segmentation": 2_913,
}


def main():
    use_compile_cache()
    print("# Table 3 — MSCOCO / PASCAL (CPU, per-dataset seconds)")
    print("dataset,kernel,conv_s,prop_s,speedup")
    for r in run(groups=DATASETS):
        print(f"{r['group']},{r['kernel']}x{r['kernel']}x3,"
              f"{r['conv_s_dataset']:.2f},{r['prop_s_dataset']:.2f},"
              f"{r['speedup']:.3f}")


if __name__ == "__main__":
    main()
