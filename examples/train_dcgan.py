"""End-to-end driver: train a (reduced) DC-GAN whose generator runs on the
unified kernel-segregated transpose convolution — the paper's own workload.

Non-saturating GAN loss on synthetic band-limited images, AdamW for both
nets, a few hundred steps on CPU. The generator runs on a **compiled
execution plan** (:mod:`repro.kernels.plan`): the whole layer stack's
dispatch — forward method + tiles, backward method + tiles per layer — is
resolved ONCE from the autotune cache (``train=True``: the jointly-tuned
full-train-step winners) before the train step is traced, and the step
closes over the immutable plan; no per-call cache consult ever runs inside
the training loop. ``--tune`` pre-populates the cache for the reduced layer
shapes first, so the plan compiles against measured winners instead of the
cold-cache napkin rule. Per-step wall time is logged via
:class:`repro.timing.StepTimer`, so the example doubles as an end-to-end
training-speed repro.

Run:  PYTHONPATH=src python examples/train_dcgan.py [--steps 200] [--tune]
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.data import SyntheticImages
from repro.models import gan
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.timing import StepTimer


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--method", default="auto",
                    choices=["auto", "unified", "conventional", "pallas",
                             "pallas_phase"],
                    help="'auto' (default) consults the autotuner cache per "
                         "layer shape in training mode — the jointly-tuned "
                         "fwd+bwd step winner (napkin-rule fallback when "
                         "cold)")
    ap.add_argument("--tune", action="store_true",
                    help="jointly tune (fwd+bwd+step) the reduced layer "
                         "shapes before tracing the train step")
    args = ap.parse_args()

    # reduced DC-GAN (channels/16) => 32x32 outputs, CPU-friendly
    cfg = dataclasses.replace(
        gan.DCGAN,
        layers=tuple((hw, cin // 16, max(cout // 16, 3) if i == 3 else cout // 16)
                     for i, (hw, cin, cout) in enumerate(gan.DCGAN.layers[:3])),
    )
    out_hw = cfg.out_hw(cfg.layers[-1][0])
    out_c = cfg.layers[-1][2]
    print(f"[dcgan] generator -> {out_hw}x{out_hw}x{out_c}, "
          f"method={args.method}")

    if args.tune:
        # tune BEFORE the jitted step is traced: the outer jit pins whatever
        # the cache says at trace time (docs/AUTOTUNE.md). Each layer tunes
        # as the full act(tconv + b) unit — the same epilogue'd signature
        # generator_plan compiles below.
        from repro.kernels import autotune

        epis = gan.generator_epilogues(cfg)
        for (hw, cin, cout), epi in zip(cfg.layers, epis):
            rec = autotune.tune_layer(
                args.batch, hw, cfg.kernel, cin, cout, cfg.padding,
                train=True, epilogue=epi,
            )
            print(f"[tune] {hw}x{hw}x{cin}->{cout} [{epi.tag()}]: "
                  f"fwd={rec['fwd']['method']} bwd={rec['bwd']['method']} "
                  f"step={rec['step']['method']}")

    # compile the whole generator's execution plan ONCE, after tuning and
    # before the train step is traced: the step closes over the immutable
    # plan, so dispatch work never runs inside the loop and retuning can
    # only take effect through an explicit recompile
    gp = gan.generator_init(jax.random.key(0), cfg)
    train_plan = gan.generator_plan(
        cfg, args.batch, train=True, method=args.method
    )
    print(train_plan.describe())
    dp = gan.discriminator_init(jax.random.key(1), out_hw, out_c)
    opt_cfg = AdamWConfig(lr=2e-4, b1=0.5, b2=0.999, weight_decay=0.0)
    g_opt = adamw_init(gp, opt_cfg)
    d_opt = adamw_init(dp, opt_cfg)
    data = SyntheticImages(hw=out_hw, channels=out_c,
                           global_batch=args.batch)

    def d_loss_fn(dp, gp, real, z):
        fake = gan.generator_apply(gp, cfg, z, plan=train_plan)
        d_real = gan.discriminator_apply(dp, real)
        d_fake = gan.discriminator_apply(dp, fake)
        return (
            jnp.mean(jax.nn.softplus(-d_real))
            + jnp.mean(jax.nn.softplus(d_fake))
        )

    def g_loss_fn(gp, dp, z):
        fake = gan.generator_apply(gp, cfg, z, plan=train_plan)
        return jnp.mean(jax.nn.softplus(-gan.discriminator_apply(dp, fake)))

    @jax.jit
    def step(gp, dp, g_opt, d_opt, real, z):
        dl, dg = jax.value_and_grad(d_loss_fn)(dp, gp, real, z)
        dp, d_opt, _ = adamw_update(dg, d_opt, dp, opt_cfg, opt_cfg.lr)
        gl, gg = jax.value_and_grad(g_loss_fn)(gp, dp, z)
        gp, g_opt, _ = adamw_update(gg, g_opt, gp, opt_cfg, opt_cfg.lr)
        return gp, dp, g_opt, d_opt, gl, dl

    timer = StepTimer()
    for i in range(args.steps):
        real = data.batch(i)
        z = jax.random.normal(jax.random.fold_in(jax.random.key(7), i),
                              (args.batch, cfg.z_dim))
        gp, dp, g_opt, d_opt, gl, dl = jax.block_until_ready(
            step(gp, dp, g_opt, d_opt, real, z)
        )
        dt = timer.tick()
        if i % 20 == 0:
            print(f"step {i:4d}  g_loss {float(gl):.4f}  "
                  f"d_loss {float(dl):.4f}  step {dt * 1e3:.1f}ms  "
                  f"(mean {timer.mean() * 1e3:.1f}ms)")
    print(f"[dcgan] steady-state step time: mean {timer.mean() * 1e3:.2f}ms "
          f"median {timer.median() * 1e3:.2f}ms over {len(timer.steps)} steps")
    # eval plan: batch 1, inference-mode winners (fwd entries, not step)
    eval_plan = gan.generator_plan(cfg, 1, method=args.method)
    img = gan.generator_apply(
        gp, cfg, jax.random.normal(jax.random.key(9), (1, cfg.z_dim)),
        plan=eval_plan,
    )
    print(f"[dcgan] done: sample range [{float(img.min()):.3f}, "
          f"{float(img.max()):.3f}], finite={bool(jnp.all(jnp.isfinite(img)))}")


if __name__ == "__main__":
    main()
