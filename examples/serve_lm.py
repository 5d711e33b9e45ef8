"""Serving example: batched prefill + decode with the KV-cache runtime.

Loads (or trains briefly) a small LM, then serves a batch of requests:
prefill all prompts at once, decode N tokens autoregressively with
per-sequence positions — the same serve_step the decode_32k / long_500k
dry-run cells lower at production scale.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.data import SyntheticTokens
from repro.models.lm import build_model

BATCH, PROMPT, GEN = 4, 24, 16


def main():
    use_compile_cache()
    cfg = dataclasses.replace(
        get_config("qwen2-0.5b"),
        d_model=256, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=512,
        vocab_size=4_096, remat=False, attn_chunk=64,
    )
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    print(f"[serve] {cfg.name}-reduced {cfg.param_count() / 1e6:.1f}M params; "
          f"batch={BATCH} prompt={PROMPT} gen={GEN}")

    prompts = SyntheticTokens(cfg.vocab_size, PROMPT, BATCH).batch(0)["tokens"]

    prefill = jax.jit(lambda p, b: model.prefill(p, b))
    decode = jax.jit(lambda p, c, b: model.decode_step(p, c, b))

    t0 = time.time()
    logits, cache = prefill(params, {"tokens": prompts})
    # extend the cache to hold the generated tokens
    cache = jax.tree_util.tree_map(
        lambda a: jnp.pad(
            a, [(0, 0)] * 2 + [(0, GEN)] + [(0, 0)] * (a.ndim - 3)
        ) if a.ndim >= 4 else a,
        cache,
    )
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits[:, -1], -1)[:, None]
    out = [tok]
    t0 = time.time()
    for t in range(PROMPT, PROMPT + GEN - 1):
        logits, cache = decode(
            params, cache, {"tokens": tok, "pos": jnp.full((BATCH,), t)}
        )
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out, axis=1)
    print("generated token ids (greedy):")
    for i in range(BATCH):
        print(f"  seq{i}: {list(map(int, gen[i]))}")
    print(f"[serve] prefill {t_prefill * 1e3:.1f} ms "
          f"({BATCH * PROMPT} tokens), decode "
          f"{t_decode / (GEN - 1) * 1e3:.1f} ms/token (incl. jit warmup)")


if __name__ == "__main__":
    main()
