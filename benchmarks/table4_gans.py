"""Paper Table 4 (ablation): transpose-conv layers of DC-GAN/DiscoGAN,
ArtGAN, GP-GAN, EB-GAN — per-layer conventional vs unified timing, total
speedup, and memory savings.

Since the backward pass landed this covers *training*, not just the paper's
forward-only column: per layer it reports forward, backward (``jax.vjp``
application), and full-train-step (``value_and_grad``) seconds for every
trainable method — ``auto`` running in training mode, which dispatches the
jointly-tuned step winner when the cache was pre-tuned
(``python -m repro.kernels.autotune --gan-zoo --train``) and the
napkin-rule fallback when cold. The rows are merged into
``BENCH_transpose_conv.json`` under the ``table4_train`` key (the file's
other sections, written by ``benchmarks.transpose_conv_bench``, are
preserved).
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core import memory_savings_bytes, transpose_conv2d
from repro.models.gan import GAN_ZOO
from benchmarks.common import time_fn
from repro.compile_cache import use_compile_cache


METHODS = ("naive", "conventional", "unified", "auto")
# naive (tap-by-tap reference) is forward-only; the rest race all three
# directions
TRAIN_METHODS = ("conventional", "unified", "auto")


def run_model(cfg):
    """Times per layer for: naive (paper's actual baseline style — explicit
    upsample + tap-by-tap accumulation), conventional (XLA conv over the
    upsampled map), unified (paper's contribution), auto (ours: per-layer
    autotuned dispatch, §Perf) — forward, backward, and full train step."""
    from repro.kernels.ref import conventional_ref

    rows = []
    tot = {m: 0.0 for m in METHODS}
    tot_bwd = {m: 0.0 for m in TRAIN_METHODS}
    tot_step = {m: 0.0 for m in TRAIN_METHODS}
    tot_mem = 0.0
    for i, (hw, cin, cout) in enumerate(cfg.layers):
        x = jax.random.normal(jax.random.key(i), (1, hw, hw, cin))
        k = jax.random.normal(jax.random.key(100 + i),
                              (cfg.kernel, cfg.kernel, cin, cout)) * 0.05
        fns = {
            "naive": jax.jit(lambda x, k: conventional_ref(x, k, cfg.padding)),
            **{m: jax.jit(
                lambda x, k, m=m: transpose_conv2d(x, k, cfg.padding, method=m)
            ) for m in METHODS[1:]},
        }
        want = fns["conventional"](x, k)
        ts = {}
        for m, f in fns.items():
            got = f(x, k)
            assert float(jnp.max(jnp.abs(got - want))) < 1e-3, m
            ts[m] = time_fn(f, x, k)
            tot[m] += ts[m]

        # backward (vjp application) + full step per trainable method
        g = jax.random.normal(jax.random.key(200 + i), want.shape)
        ts_bwd, ts_step = {}, {}
        for m in TRAIN_METHODS:
            train = m == "auto"

            def fwd(x, k, _m=m, _t=train):
                return transpose_conv2d(
                    x, k, cfg.padding, method=_m, train=_t
                )

            bwd = jax.jit(lambda x, k, g: jax.vjp(fwd, x, k)[1](g))
            ts_bwd[m] = time_fn(bwd, x, k, g)
            tot_bwd[m] += ts_bwd[m]
            step = jax.jit(jax.value_and_grad(
                lambda x, k: fwd(x, k).sum(), argnums=(0, 1)
            ))
            ts_step[m] = time_fn(step, x, k)
            tot_step[m] += ts_step[m]

        # Table 4 counts the whole upsampled buffer as the saving
        mem = memory_savings_bytes(hw, cin, 4, cfg.padding, mode="buffer")
        tot_mem += mem
        rows.append((f"{hw}x{hw}x{cin}", ts, ts_bwd, ts_step, mem))
    return rows, tot, tot_bwd, tot_step, tot_mem


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="BENCH_transpose_conv.json",
                    help="artifact to merge the table4_train rows into")
    args = ap.parse_args(argv)

    print("# Table 4 — GAN transpose-conv layers (fwd / bwd / full step, "
          "1 sample)")
    print("model,layer,naive_s,conv_s,unified_s,auto_s,"
          "bwd_conv_s,bwd_unified_s,bwd_auto_s,"
          "step_conv_s,step_unified_s,step_auto_s,"
          "speedup_vs_naive,step_speedup_vs_xla,mem_savings_bytes")
    artifact = {"backend": jax.default_backend(), "models": {}}
    for name, cfg in GAN_ZOO.items():
        rows, tot, tot_bwd, tot_step, mem = run_model(cfg)
        model_rows = []
        for layer, ts, ts_bwd, ts_step, m in rows:
            print(f"{name},{layer},{ts['naive']:.5f},{ts['conventional']:.5f},"
                  f"{ts['unified']:.5f},{ts['auto']:.5f},"
                  f"{ts_bwd['conventional']:.5f},{ts_bwd['unified']:.5f},"
                  f"{ts_bwd['auto']:.5f},"
                  f"{ts_step['conventional']:.5f},{ts_step['unified']:.5f},"
                  f"{ts_step['auto']:.5f},"
                  f"{ts['naive'] / ts['auto']:.3f},"
                  f"{ts_step['conventional'] / ts_step['auto']:.3f},{int(m)}")
            model_rows.append({
                "layer": layer, "fwd_s": ts, "bwd_s": ts_bwd,
                "step_s": ts_step, "mem_savings_bytes": int(m),
            })
        print(f"{name},TOTAL,{tot['naive']:.5f},{tot['conventional']:.5f},"
              f"{tot['unified']:.5f},{tot['auto']:.5f},"
              f"{tot_bwd['conventional']:.5f},{tot_bwd['unified']:.5f},"
              f"{tot_bwd['auto']:.5f},"
              f"{tot_step['conventional']:.5f},{tot_step['unified']:.5f},"
              f"{tot_step['auto']:.5f},"
              f"{tot['naive'] / tot['auto']:.3f},"
              f"{tot_step['conventional'] / tot_step['auto']:.3f},{int(mem)}")
        artifact["models"][name] = {
            "layers": model_rows,
            "fwd_totals": tot, "bwd_totals": tot_bwd,
            "step_totals": tot_step, "mem_savings_bytes": int(mem),
        }

    out_path = Path(args.out)
    blob = {}
    if out_path.exists():
        try:
            blob = json.loads(out_path.read_text())
        except (json.JSONDecodeError, OSError):
            blob = {}
    blob["table4_train"] = artifact
    out_path.write_text(json.dumps(blob, indent=1, sort_keys=True))
    print(f"# merged table4_train into {args.out}")


if __name__ == "__main__":
    main()
