"""FLOP / memory model validation: analytic MAC reduction + pallas-vs-lax
parity on paper-shaped layers (interpret mode, correctness-oriented)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import flop_count, memory_savings_bytes, transpose_conv2d
from repro.models.gan import GAN_ZOO, generator_flops
from repro.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    print("# FLOP model — conventional vs segregated MACs")
    print("case,conv_MACs,seg_MACs,reduction")
    for n_in, n_k, pad in [(224, 3, 0), (224, 4, 0), (224, 5, 0),
                           (4, 4, 1), (32, 4, 1)]:
        c = flop_count(n_in, n_k, 3, 3, pad, method="conventional")
        s = flop_count(n_in, n_k, 3, 3, pad, method="segregated")
        print(f"N{n_in}_k{n_k}_P{pad},{c},{s},{c / s:.3f}")
    print()
    print("# GAN generators — full-stack MACs (Table 4 models)")
    print("model,conv_MACs,seg_MACs,reduction,mem_savings_bytes")
    for name, cfg in GAN_ZOO.items():
        # bare transpose-conv MACs: the paper's exact-4x algebra (the
        # default additionally counts the epilogue's element ops)
        c = generator_flops(cfg, method="conventional",
                            include_epilogue=False)
        s = generator_flops(cfg, method="segregated",
                            include_epilogue=False)
        mem = sum(memory_savings_bytes(hw, cin, 4, cfg.padding)
                  for hw, cin, _ in cfg.layers)
        print(f"{name},{c},{s},{c / s:.3f},{mem}")
    print()
    print("# pallas kernel parity (interpret mode)")
    x = jax.random.normal(jax.random.key(0), (1, 16, 16, 8))
    k = jax.random.normal(jax.random.key(1), (4, 4, 8, 8)) * 0.1
    a = transpose_conv2d(x, k, 1, method="unified")
    b = transpose_conv2d(x, k, 1, method="pallas")
    print("pallas_max_err,", float(jnp.max(jnp.abs(a - b))))


if __name__ == "__main__":
    main()
