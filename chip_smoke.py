"""Bring-up smoke test: the system's main paths on a TPU at Table-4 widths.

    python chip_smoke.py              # one chip: serve, kernels, train
    python chip_smoke.py --chips 4    # four chips: sharded serving only

One chip runs three phases through the entry points a user calls:

* serve   — a ``GanEngine`` serves EB-GAN (Table 4 widths, fp32) with
            buckets 1..16 after ``warmup()``; every request must finish,
            with no retries and no recompiles after warmup.
* kernels — DC-GAN at batch 8, each Pallas forward pinned per layer
            through ``generator_plan(..., fuse="off")``, plus one
            ``value_and_grad`` through the train plan whose backward is the
            Pallas dx/dw pair.
* train   — ``GanTrainer`` on DC-GAN (64x64x3) for 4 steps of batch 32.

Every output is checked for finiteness and against a plain fp32 reference:
the ``conventional`` generator under ``jax.default_matmul_precision
("highest")``. Four chips run only EB-GAN through a ``ReplicaSupervisor``
whose replica shards each bucket over a (pod, data) 2x2 mesh, compared with
the one-chip ``GanEngine`` on the same latents.

The script runs in one process and never falls back to the CPU: without a
TPU it exits non-zero before any phase. Weights and latents come from
``--seed``. The plan is the cold TPU plan: the autotune cache is a file this
script creates empty under ``chiprun_out/chip_smoke/``. The last line of
standard output is the JSON result; any failed check exits non-zero before
it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

# TPU default precision runs each fp32 matmul as one bf16 pass (8-bit
# mantissa), so a layer's output carries ~2^-9 relative rounding per
# product, summed over 1024-8192 products and compounded through 4-6
# layers. The bound is on max|out - ref| / max|ref|.
REL_TOL = 3e-2
REL_TOL_REASON = (
    "fp32 operands run as single bf16 MXU passes at default precision: "
    "~2^-9 relative per product, compounded over 4-6 layers"
)
# The gradient check runs both sides at highest precision. At default
# precision a relu input within rounding of 0 flips its mask between the two
# sides and moves a whole column of the projection's gradient (a 0.38
# relative error on a v5e); at highest only fp32 summation order differs.
GRAD_REL_TOL = 1e-3
GRAD_REL_TOL_REASON = (
    "both sides at highest precision: fp32 summation order only"
)


def rel_err(out, ref) -> tuple[float, float]:
    """(max abs error, max abs error / max |ref|)."""
    import numpy as np

    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    err = float(np.max(np.abs(out - ref)))
    return err, err / max(float(np.max(np.abs(ref))), 1e-30)


def check(name: str, out, ref, tol: float = REL_TOL) -> None:
    import numpy as np

    if not np.isfinite(np.asarray(out)).all():
        raise AssertionError(f"{name}: non-finite output")
    if np.shape(out) != np.shape(ref):
        raise AssertionError(
            f"{name}: shape {np.shape(out)} != reference {np.shape(ref)}"
        )
    err, rel = rel_err(out, ref)
    print(f"  {name}: max abs err {err:.3e}, rel {rel:.3e} (tol {tol})")
    if not rel <= tol:
        raise AssertionError(f"{name}: rel err {rel:.3e} > {tol}")


def reference_generator(cfg):
    """The plain fp32 reference: jitted conventional generator at highest
    matmul precision."""
    import jax

    from repro.models import gan

    fn = jax.jit(lambda p, z: gan.generator_apply(
        p, cfg, z, method="conventional"))

    def run(params, z):
        with jax.default_matmul_precision("highest"):
            return fn(params, z)

    return run


def latents(rng, n: int, z_dim: int):
    import numpy as np

    return rng.standard_normal((n, z_dim)).astype(np.float32)


def phase_serve(seed: int) -> None:
    import jax
    import numpy as np

    from repro.kernels.plan import FusedPairPlan
    from repro.models import gan
    from repro.serve import BucketPolicy, GanEngine, GenRequest
    from repro.serve.batching import pow2_buckets

    cfg = gan.EBGAN
    params = gan.generator_init(jax.random.key(seed), cfg)
    eng = GanEngine(BucketPolicy(buckets=pow2_buckets(16)), dtype="float32")
    eng.register(cfg, params)
    t0 = time.perf_counter()
    eng.warmup()
    print(f"[setup] serve warmup (plans + compile of "
          f"{len(eng.policy.buckets)} buckets): "
          f"{time.perf_counter() - t0:.1f} s")
    plan16 = eng.registry[cfg.name].plans[16]
    print(plan16.describe())
    if not any(isinstance(e, FusedPairPlan) for e in plan16.entries):
        raise AssertionError("cold TPU plan fused no layer pair")

    rng = np.random.default_rng(seed)
    reqs = [GenRequest(cfg.name, latents(rng, int(n), cfg.z_dim))
            for n in rng.integers(1, 5, size=8)]
    t0 = time.perf_counter()
    eng.serve(reqs)
    print(f"  served {len(reqs)} requests ({sum(r.n for r in reqs)} samples)"
          f" in {time.perf_counter() - t0:.2f} s")
    states = [r.terminal_state for r in reqs]
    if states != ["done"] * len(reqs) or any(r.retries for r in reqs):
        raise AssertionError(f"request states {states}")
    extra = eng.metrics.recompiles - eng.warmup_recompiles
    if extra:
        raise AssertionError(f"{extra} recompiles after warmup")
    ref = reference_generator(cfg)
    out = np.concatenate([r.output for r in reqs])
    t0 = time.perf_counter()
    want = np.asarray(ref(params, np.concatenate([r.z for r in reqs])))
    print(f"[setup] reference compile + run: {time.perf_counter() - t0:.1f} s")
    check("serve ebgan fp32 vs reference", out, want)


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import gan

    cfg, batch = gan.DCGAN, 8
    params = gan.generator_init(jax.random.key(seed + 1), cfg)
    z = jnp.asarray(latents(np.random.default_rng(seed + 1), batch,
                            cfg.z_dim))
    ref = reference_generator(cfg)
    want = ref(params, z)
    for method, resolved in (("pallas", "pallas_fused"),
                             ("pallas_phase", "pallas_phase"),
                             ("pallas_gemm", "pallas_gemm")):
        plan = gan.generator_plan(cfg, batch, method=method, fuse="off")
        got = {e.method for e in plan.entries}
        if got != {resolved}:
            raise AssertionError(f"{method} plan resolved to {got}")
        t0 = time.perf_counter()
        out = jax.jit(lambda p, z, plan=plan: gan.generator_apply(
            p, cfg, z, plan=plan))(params, z)
        out = jax.block_until_ready(out)
        print(f"[setup] {resolved} generator compile + first run: "
              f"{time.perf_counter() - t0:.1f} s")
        check(f"dcgan b{batch} {resolved} vs reference", out, want)

    # the train plan pins the fused forward; its backward is the cold
    # choice on a TPU, the Pallas dx/dw kernels
    plan = gan.generator_plan(cfg, batch, train=True, method="pallas")
    print(plan.describe())
    if {lp.bwd_method for lp in plan} != {"pallas"}:
        raise AssertionError("train plan backward is not the Pallas one")
    ct = jax.random.normal(jax.random.key(seed + 2), want.shape)

    def loss(p, **kw):
        return jnp.sum(gan.generator_apply(p, cfg, z, **kw) * ct)

    with jax.default_matmul_precision("highest"):
        val, grads = jax.jit(jax.value_and_grad(
            lambda p: loss(p, plan=plan)))(params)
        rval, rgrads = jax.jit(jax.value_and_grad(
            lambda p: loss(p, method="conventional")))(params)
    print(f"gradient tolerance {GRAD_REL_TOL}: {GRAD_REL_TOL_REASON}")
    check("dcgan value_and_grad loss", val, rval, GRAD_REL_TOL)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        rg = rgrads
        for k in path:
            rg = rg[k.key]
        check("grad " + "/".join(k.key for k in path), g, rg, GRAD_REL_TOL)


def phase_train(seed: int) -> None:
    import jax
    import numpy as np

    from repro.data import SyntheticImages
    from repro.models import gan
    from repro.train.gan_trainer import GanTrainer, GanTrainerConfig

    cfg, batch, steps = gan.DCGAN, 32, 4
    hw = cfg.out_hw(cfg.layers[-1][0])
    data = SyntheticImages(hw=hw, channels=cfg.layers[-1][2],
                           global_batch=batch, seed=seed)
    trainer = GanTrainer(cfg, GanTrainerConfig(global_batch=batch,
                                               log_every=1), data)
    print(trainer.train_plan.describe())
    state = trainer.init_state(jax.random.key(seed))
    t0 = time.perf_counter()
    state, hist = trainer.run(state, steps=steps)
    jax.block_until_ready(state)
    print(f"[setup] {steps} train steps of {hw}x{hw}x3, batch {batch}, "
          f"compile included: {time.perf_counter() - t0:.1f} s")
    losses = [(h["g_loss"], h["d_loss"]) for h in hist]
    if len(hist) != steps or not np.isfinite(losses).all():
        raise AssertionError(f"train history {hist}")
    if trainer.skipped_steps:
        raise AssertionError(f"{trainer.skipped_steps} skipped steps")


def phase_sharded(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.models import gan
    from repro.serve import BucketPolicy, GanEngine, GenRequest
    from repro.serve.replica import Replica
    from repro.serve.supervisor import ReplicaSupervisor

    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {devices}")
    mesh = Mesh(np.array(devices).reshape(2, 2), ("pod", "data"))
    cfg, bucket = gan.EBGAN, 16
    params = gan.generator_init(jax.random.key(seed), cfg)
    policy = BucketPolicy(buckets=(bucket,))
    replica = Replica("mesh", shard=True, mesh=mesh)
    # a generous dispatch timeout: this run checks routing and results,
    # and a host hiccup must not turn into a retry
    sup = ReplicaSupervisor([replica], policy, timeout_s=120.0)
    sup.register(cfg, params)
    t0 = time.perf_counter()
    sup.warmup()
    print(f"[setup] sharded replica warmup: {time.perf_counter() - t0:.1f} s")
    out = replica._executable(cfg.name, bucket)(
        params, np.zeros((bucket, cfg.z_dim), np.float32))
    n_dev = len(out.sharding.device_set)
    print(f"  executable output sharding spans {n_dev} devices: "
          f"{out.sharding}")
    if n_dev != 4:
        raise AssertionError("sharded replica ran unsharded")

    one = GanEngine(policy)
    one.register(cfg, params)
    one.warmup()
    rng = np.random.default_rng(seed)
    zs = [latents(rng, int(n), cfg.z_dim) for n in rng.integers(1, 5, 8)]
    sharded = sup.serve([GenRequest(cfg.name, z) for z in zs])
    single = one.serve([GenRequest(cfg.name, z) for z in zs])
    if not all(r.done for r in sharded + single):
        raise AssertionError("a request did not finish")
    m = sup.metrics
    if m.retries or m.degraded_batches:
        raise AssertionError(
            f"retries={m.retries} degraded_batches={m.degraded_batches}")
    print(f"  retries={m.retries} degraded_batches={m.degraded_batches}")
    check("4-chip sharded vs 1-chip engine",
          np.concatenate([r.output for r in sharded]),
          np.concatenate([r.output for r in single]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    cache = OUT_DIR / "autotune.json"
    cache.unlink(missing_ok=True)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(cache)
    print(f"compile cache: {use_compile_cache()}")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}")
    print(f"reference tolerance {REL_TOL}: {REL_TOL_REASON}")
    phases = (
        (("sharded", phase_sharded),) if args.chips == 4 else
        (("serve", phase_serve), ("kernels", phase_kernels),
         ("train", phase_train))
    )
    for name, fn in phases:
        t0 = time.perf_counter()
        print(f"== {name}", flush=True)
        fn(args.seed)
        print(f"== {name} ok ({time.perf_counter() - t0:.1f} s)", flush=True)
    stats = dev.memory_stats() or {}
    print(f"peak device memory: {stats.get('peak_bytes_in_use')} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
