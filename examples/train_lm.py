"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps with
the full substrate — fault-tolerant Trainer, deterministic data, step-atomic
checkpoints, cosine schedule, optional int8 gradient compression.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses

import jax

from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.data import SyntheticTokens
from repro.models.lm import build_model
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import TrainConfig, init_train_state, make_train_step
from repro.train.trainer import Trainer


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()

    # xlstm-125m at reduced width => ~10M params; same family/period
    # structure as the full config (d_model 768 -> 256 for CPU speed)
    cfg = dataclasses.replace(
        get_config("xlstm-125m"),
        d_model=256, n_layers=4, n_heads=4, vocab_size=8_192,
        remat=False, attn_chunk=64,
    )
    model = build_model(cfg)
    print(f"[train_lm] {cfg.name}-reduced: {cfg.param_count() / 1e6:.1f}M params")

    tc = TrainConfig(
        optimizer=AdamWConfig(lr=1e-3),
        warmup_steps=20,
        total_steps=args.steps,
        compress_grads=args.compress_grads,
    )
    params, opt = init_train_state(model, jax.random.key(0), tc)
    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=args.seq,
                           global_batch=args.batch)
    trainer = Trainer(
        model, make_train_step(model, tc), data,
        ckpt_dir=args.ckpt_dir, ckpt_every=100, log_every=20,
    )
    params, opt, history = trainer.run(params, opt, steps=args.steps)
    print(f"[train_lm] loss {history[0]:.4f} -> {history[-1]:.4f}; "
          f"checkpoints in {args.ckpt_dir} (re-run to resume)")


if __name__ == "__main__":
    main()
