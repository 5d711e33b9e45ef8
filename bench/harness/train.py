"""Training cells: ``GanTrainer.run`` steps the G+D job.

Set-up builds one trainer and its state from the benchmark's weights,
compiles the step, and drives the first ``check_steps`` steps through the
same ``run`` call the window uses, copying out what the comparison needs:
the losses, the first gradient as the optimizer took it (its first moment
after one step over ``1 - b1``), and the parameters after the last of
them. The window then continues the same trainer on the same state.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time

import numpy as np

from harness import loadgen, system
from harness import trace as tracelib
from harness.serve import gc_watch, peak_bytes

# steps per call of ``run`` in the window, as seconds of work
CHUNK_S = 0.25


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Images:
    """Real images from the seed: one jitted call per batch, each batch
    its own rows (``fold_in`` of the batch index)."""

    def __init__(self, seed: int, batch: int, hw: int, channels: int):
        import jax

        key = jax.random.key(loadgen.jax_seed(seed) ^ 0x5EED)
        shape = (batch, hw, hw, channels)
        self._fn = jax.jit(lambda i: jax.random.uniform(
            jax.random.fold_in(key, i), shape, minval=-1.0, maxval=1.0))

    def batch(self, index: int):
        return self._fn(index)


def _steps(trainer, state, start: int, stop: int):
    """``trainer.run`` from step ``start`` to ``stop`` on the state in
    memory: ``run`` resumes from what ``resume`` returns, so that is where
    the step count is handed over (no checkpoint is written)."""
    trainer.resume = lambda s: (start, s)
    return trainer.run(state, steps=stop)


def _leaves(tree, prefix: str) -> dict:
    import jax

    return {prefix + "/".join(str(getattr(k, "key", k)) for k in path):
            np.asarray(leaf, np.float64)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def run_training(run, t_start: float, trace: bool, planted=None) -> dict:
    """Set up, measure, check. Fills ``run``; returns the program's copied
    readings for the control script. ``planted`` (control script and
    tests only) wraps the trainer after it is built."""
    import jax

    system.import_program()
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.train.gan_trainer import GanTrainer, GanTrainerConfig

    cfg, mix = run.cell.config, run.cell.traffic
    ref = system.reference(cfg)
    gcfg = system.program_config(cfg)
    opt = AdamWConfig(**mix["optimizer"])
    batch = mix["global_batch"]
    hw = gcfg.out_hw(gcfg.layers[-1][0])
    data = Images(run.seed, batch, hw, gcfg.layers[-1][2])
    zseed = loadgen.jax_seed(run.seed) >> 1
    trainer = GanTrainer(
        gcfg, GanTrainerConfig(global_batch=batch, opt=opt, z_seed=zseed,
                               dtype=cfg["dtype"]),
        data, log_fn=lambda *a: None)
    if planted is not None:
        planted(trainer)
    run.notes["setup_start_s"] = time.perf_counter() - t_start
    gp, dp = jax.block_until_ready(system.gan_weights(ref, cfg, run.seed))
    run.notes["setup_weights_s"] = time.perf_counter() - t_start
    host0 = {**_leaves(gp, "g/"), **_leaves(dp, "d/")}
    state = {"g_params": gp, "d_params": dp,
             "g_opt": adamw_init(gp, opt), "d_opt": adamw_init(dp, opt)}
    del gp, dp

    # the first steps, through the window's own call
    n_check = mix["check_steps"]
    state, hist = _steps(trainer, state, 0, 1)
    run.notes["setup_first_step_s"] = time.perf_counter() - t_start
    grads = {k: v / (1 - opt.b1) for k, v in
             {**_leaves(state["g_opt"]["m"], "g/"),
              **_leaves(state["d_opt"]["m"], "d/")}.items()}
    state, more = _steps(trainer, state, 1, n_check)
    hist = hist + more
    after = {**_leaves(state["g_params"], "g/"),
             **_leaves(state["d_params"], "d/")}
    mine = {"losses": [(h["g_loss"], h["d_loss"]) for h in hist],
            "grads": grads,
            "change": {k: after[k] - host0[k] for k in host0}}
    step_s = float(np.median(trainer.timer.steps[1:] or [0.01]))
    chunk = max(1, round(CHUNK_S / max(step_s, 1e-4)))
    state = jax.block_until_ready(state)
    run.setup_s = time.perf_counter() - t_start

    ann = system.annotate(trace)
    steps, skipped, step = 0, 0, n_check
    prof = tracelib.profiled() if trace else contextlib.nullcontext({})
    with prof as got, gc_watch() as gcs:
        with ann("bench.window"):
            t0 = time.perf_counter()
            while True:
                with ann("trainer.run"):
                    state, hist = _steps(trainer, state, step, step + chunk)
                step += chunk
                steps += len(hist)
                skipped += sum(h["skipped"] for h in hist)
                if time.perf_counter() - t0 >= run.seconds:
                    break
            t_end = time.perf_counter()
    run.trace = got.get("trace")
    run.notes.update(gcs)
    run.window_s = t_end - t0
    run.steps = steps
    run.attempted, run.failed = steps, skipped
    run.device["memory_peak_bytes"] = peak_bytes()
    log(f"[train] {steps} steps in {run.window_s:.3f} s, {chunk} per call, "
        f"{skipped} skipped")
    del state, trainer
    gc.collect()

    t1 = time.perf_counter()
    where = {}
    got = compare(mine, ref, cfg, mix, run.seed, data, zseed, where)
    log(f"[check] reference over {n_check} steps: "
        f"{time.perf_counter() - t1:.2f} s")
    for name, value in got.items():
        run.checks[name] = (value, cfg["limits"][name])
    log(f"[check] worst leaves {where}")
    return mine


def compare(mine, ref, cfg, mix, seed, data, zseed, where=None) -> dict:
    """:func:`readings` against the reference at ``highest`` precision,
    and ``grad_gap_default``: the first gradient's gap against the
    reference's first step at the precision the configuration states
    (``matmul_precision``; at ``default`` each product of
    bfloat16-rounded inputs, summed in float32). Passes kept in bfloat16
    round once more per layer, and only this number sees that."""
    from jax import lax

    want = reference_readings(ref, cfg, mix, seed, data, zseed)
    out = readings(mine, want, where)
    first = reference_readings(ref, cfg, mix, seed, data, zseed, steps=1,
                               precision=lax.Precision(
                                   cfg["matmul_precision"]))["grads"]
    out["grad_gap_default"], leaf = worst_leaf(mine["grads"], first,
                                               list(first))
    if where is not None:
        where["grad_gap_default"] = leaf
    return out


def step_inputs(data, zseed: int, batch: int, z_dim: int, step: int):
    """The step's real images and latents, as the trainer draws them."""
    import jax

    z = jax.random.normal(jax.random.fold_in(jax.random.key(zseed), step),
                          (batch, z_dim))
    return data.batch(step), z


def reference_readings(ref, cfg, mix, seed, data, zseed, dtype=None,
                       steps=None, **how) -> dict:
    """The reference's losses, first gradients and parameter changes over
    the checked steps (or the first ``steps``), from the same weights and
    inputs. ``dtype`` runs the reference in that type (a control);
    ``how`` passes ``precision`` or a ``compute`` type on to its
    ``train``."""
    import jax.numpy as jnp

    gp, dp = system.gan_weights(ref, cfg, seed)
    host0 = {**_leaves(gp, "g/"), **_leaves(dp, "d/")}
    batches = [step_inputs(data, zseed, mix["global_batch"], cfg["z_dim"], t)
               for t in range(steps or mix["check_steps"])]
    losses, first, gp, dp = ref.train(gp, dp, cfg, mix["optimizer"], batches,
                                      dtype=dtype or jnp.float32, **how)
    after = {**_leaves(gp, "g/"), **_leaves(dp, "d/")}
    return {"losses": losses,
            "grads": {**_leaves(first["g"], "g/"), **_leaves(first["d"], "d/")},
            "change": {k: after[k] - host0[k] for k in host0}}


# a leaf whose reference gradient is under this share of the median
# leaf's moves by round-off alone and is left out of the change
STILL_LEAF = 1e-3


def readings(mine: dict, want: dict, where: dict | None = None) -> dict:
    """The numbers compared, each a worst case:

    * ``loss_gap`` — over the checked steps and both losses, the gap to
      the reference's loss relative to it;
    * ``grad_gap`` — over the leaves, the gap between the norms of the
      program's and the reference's first gradient, relative to the
      larger of the reference leaf's norm and the median leaf's;
    * ``update_gap`` — the same for the parameters' change over the
      checked steps, leaving out leaves the reference does not move.
    """
    loss = max(abs(a - b) / max(abs(b), 1e-12)
               for pm, pw in zip(mine["losses"], want["losses"])
               for a, b in zip(pm, pw))
    if len(mine["losses"]) != len(want["losses"]):
        loss = float("inf")
    gnorm = {k: float(np.linalg.norm(v)) for k, v in want["grads"].items()}
    med_g = float(np.median(list(gnorm.values())))
    moving = [k for k in want["change"] if gnorm[k] >= STILL_LEAF * med_g]

    def worst(name: str, a: dict, b: dict, keys) -> float:
        gap, leaf = worst_leaf(a, b, keys)
        if where is not None:
            where[name] = leaf
        return gap

    return {"loss_gap": float(loss),
            "grad_gap": worst("grad_gap", mine["grads"], want["grads"],
                              list(gnorm)),
            "update_gap": worst("update_gap", mine["change"], want["change"],
                                moving)}


def worst_leaf(a: dict, b: dict, keys) -> tuple:
    """The largest gap between the norms of ``a``'s and ``b``'s leaf,
    relative to the larger of ``b``'s leaf norm and median leaf norm, and
    its leaf."""
    norms = {k: float(np.linalg.norm(b[k])) for k in keys}
    med = float(np.median(list(norms.values())))
    gaps = {k: abs(float(np.linalg.norm(a[k])) - norms[k])
            / max(norms[k], med, 1e-30) for k in keys}
    leaf = max(gaps, key=lambda k: gaps[k])
    if not all(map(np.isfinite, gaps.values())):
        return float("inf"), leaf
    return gaps[leaf], leaf
