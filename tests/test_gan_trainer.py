"""Plan-aware fault-tolerant GAN trainer: training/resume semantics, the
NaN guard's bitwise no-op contract, int8 gradient compression with
checkpointed error feedback, and elastic gradient accumulation.

The failure-injection scenarios (kill, mid-save kill, SIGTERM, corruption)
live in tests/test_fault_injection.py; this file covers the trainer's
normal-operation contracts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import SyntheticImages
from repro.models import gan
from repro.train.checkpoint import latest_step, restore_checkpoint
from repro.train.fault_injection import FaultInjector, FaultPlan
from repro.train.gan_trainer import GanTrainer, GanTrainerConfig

TINY = gan.GANConfig("tiny", 8, ((4, 4, 4), (8, 4, 3)))
QUIET = staticmethod(lambda *a: None)


def _data(tcfg, cfg=TINY):
    micro, _ = tcfg.micro_accum
    return SyntheticImages(
        hw=cfg.out_hw(cfg.layers[-1][0]), channels=cfg.layers[-1][2],
        global_batch=micro,
    )


def _trainer(tcfg, *, ckpt_dir=None, hooks=None, data=None, cfg=TINY):
    return GanTrainer(cfg, tcfg, data if data is not None else _data(tcfg, cfg),
                      ckpt_dir=ckpt_dir, hooks=hooks, log_fn=lambda *a: None)


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


def _tree_equal(a, b) -> bool:
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    return len(leaves_a) == len(leaves_b) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(leaves_a, leaves_b)
    )


def _eager_inputs(tr, step):
    """A step's inputs built eagerly, one dispatch per op: the stacked
    data batches and ``normal(fold_in(key(z_seed), i))`` per flat index."""
    idx = [step * tr.accum + j for j in range(tr.accum)]
    reals = jnp.stack([tr.data.batch(i) for i in idx])
    zs = jnp.stack([
        jax.random.normal(
            jax.random.fold_in(jax.random.key(tr.tcfg.z_seed), i),
            (tr.micro, tr.cfg.z_dim),
        )
        for i in idx
    ])
    return reals, zs


def test_config_validation():
    with pytest.raises(ValueError):
        GanTrainerConfig(pods_alive=3, pods_total=2)
    with pytest.raises(ValueError):
        GanTrainerConfig(pods_alive=0)
    with pytest.raises(ValueError):
        GanTrainerConfig(global_batch=0)


def test_trains_and_checkpoints(tmp_path):
    tcfg = GanTrainerConfig(global_batch=2, ckpt_every=2)
    tr = _trainer(tcfg, ckpt_dir=tmp_path)
    state, hist = tr.run(tr.init_state(jax.random.key(0)), steps=4)
    assert [h["step"] for h in hist] == [0, 1, 2, 3]
    assert all(np.isfinite(h["g_loss"]) and np.isfinite(h["d_loss"])
               for h in hist)
    assert latest_step(tmp_path) == 4
    s = tr.metrics_summary()
    assert s["skipped_steps"] == 0 and s["steps_timed"] == 4


def test_resume_continues_and_trajectory_is_bit_exact(tmp_path):
    """The core resume contract WITHOUT a fault: run A to 3 (checkpointing),
    run B resumes from A's checkpoint and must reproduce the uninterrupted
    trajectory bit-for-bit."""
    tcfg = GanTrainerConfig(global_batch=2, ckpt_every=3)
    ref_tr = _trainer(tcfg)
    _, ref = ref_tr.run(ref_tr.init_state(jax.random.key(0)), steps=6)

    tr1 = _trainer(tcfg, ckpt_dir=tmp_path)
    tr1.run(tr1.init_state(jax.random.key(0)), steps=3)
    tr2 = _trainer(tcfg, ckpt_dir=tmp_path)
    _, hist2 = tr2.run(tr2.init_state(jax.random.key(0)), steps=6)

    assert tr2.resumed_step == 3
    assert [h["step"] for h in hist2] == [3, 4, 5]
    for a, b in zip([r for r in ref if r["step"] >= 3], hist2):
        assert np.float32(a["g_loss"]) == np.float32(b["g_loss"])
        assert np.float32(a["d_loss"]) == np.float32(b["d_loss"])


def test_nan_guard_leaves_state_bitwise_untouched():
    """A NaN batch must be a perfect no-op on params, optimizer moments,
    the count (LR schedule position), and the skip must be counted."""
    tcfg = GanTrainerConfig(global_batch=2)
    inj = FaultInjector(FaultPlan(nan_at_steps=(0,)))
    tr = _trainer(tcfg, hooks=inj,
                  data=inj.wrap_data(_data(tcfg), accum=1))
    state = tr.init_state(jax.random.key(1))
    before = _host(state)
    state, hist = tr.run(state, steps=1)
    assert hist[0]["skipped"] == 1
    assert tr.skipped_steps == 1
    after = _host(state)
    for part in ("g_params", "d_params", "g_opt", "d_opt"):
        assert _tree_equal(before[part], after[part]), part


def test_nan_step_then_training_continues():
    tcfg = GanTrainerConfig(global_batch=2)
    inj = FaultInjector(FaultPlan(nan_at_steps=(1,)))
    tr = _trainer(tcfg, hooks=inj,
                  data=inj.wrap_data(_data(tcfg), accum=1))
    _, hist = tr.run(tr.init_state(jax.random.key(0)), steps=4)
    assert [h["skipped"] for h in hist] == [0, 1, 0, 0]
    clean = [h for h in hist if not h["skipped"]]
    assert all(np.isfinite(h["g_loss"]) for h in clean)
    assert tr.skipped_steps == 1


def test_skipped_count_survives_checkpoint(tmp_path):
    tcfg = GanTrainerConfig(global_batch=2, ckpt_every=2)
    inj = FaultInjector(FaultPlan(nan_at_steps=(0,)))
    tr = _trainer(tcfg, ckpt_dir=tmp_path, hooks=inj,
                  data=inj.wrap_data(_data(tcfg), accum=1))
    tr.run(tr.init_state(jax.random.key(0)), steps=2)
    assert tr.skipped_steps == 1
    tr2 = _trainer(tcfg, ckpt_dir=tmp_path)
    tr2.run(tr2.init_state(jax.random.key(0)), steps=3)
    assert tr2.skipped_steps == 1   # restored from the checkpoint extra


def test_compressed_error_feedback_is_checkpointed(tmp_path):
    """compress_grads=True carries the error-feedback trees inside the
    optimizer state; the checkpoint must capture them bit-exactly and the
    compressed resume must stay on the uninterrupted trajectory."""
    tcfg = GanTrainerConfig(global_batch=2, ckpt_every=2,
                            compress_grads=True)
    ref_tr = _trainer(tcfg)
    ref_state = ref_tr.init_state(jax.random.key(0))
    assert "err" in ref_state["g_opt"] and "err" in ref_state["d_opt"]
    _, ref = ref_tr.run(ref_state, steps=4)

    tr1 = _trainer(tcfg, ckpt_dir=tmp_path)
    st1, _ = tr1.run(tr1.init_state(jax.random.key(0)), steps=2)
    # quantization error is nonzero after real steps...
    err_norm = sum(float(np.abs(np.asarray(x)).sum())
                   for x in jax.tree_util.tree_leaves(st1["g_opt"]["err"]))
    assert err_norm > 0.0
    # ...and the on-disk checkpoint holds it bit-exactly
    _, _, opt, _ = restore_checkpoint(tmp_path)
    assert _tree_equal(opt["g"]["err"], _host(st1["g_opt"]["err"]))

    tr2 = _trainer(tcfg, ckpt_dir=tmp_path)
    _, hist2 = tr2.run(tr2.init_state(jax.random.key(0)), steps=4)
    for a, b in zip([r for r in ref if r["step"] >= 2], hist2):
        assert np.float32(a["g_loss"]) == np.float32(b["g_loss"])
        assert np.float32(a["d_loss"]) == np.float32(b["d_loss"])


def test_elastic_schedule_shrinks_micro_and_accumulates():
    """Losing half the pods halves the microbatch and doubles accumulation;
    the step plan is compiled at the MICRO batch size and training runs."""
    tcfg = GanTrainerConfig(global_batch=4, pods_alive=1, pods_total=2)
    tr = _trainer(tcfg)
    assert (tr.micro, tr.accum) == (2, 2)
    assert tr.micro * tr.accum >= tcfg.global_batch
    assert tr.train_plan[0].batch == tr.micro
    _, hist = tr.run(tr.init_state(jax.random.key(0)), steps=2)
    assert len(hist) == 2 and all(np.isfinite(h["g_loss"]) for h in hist)


def test_elastic_resume_across_pod_loss(tmp_path):
    """Checkpoints are mesh/batch-schedule agnostic: a run checkpointed at
    full strength restores into a degraded (half-pods) trainer."""
    full = GanTrainerConfig(global_batch=4, ckpt_every=2)
    tr1 = _trainer(full, ckpt_dir=tmp_path)
    tr1.run(tr1.init_state(jax.random.key(0)), steps=2)

    degraded = dataclasses.replace(full, pods_alive=1, pods_total=2)
    tr2 = _trainer(degraded, ckpt_dir=tmp_path)
    _, hist = tr2.run(tr2.init_state(jax.random.key(0)), steps=4)
    assert tr2.resumed_step == 2
    assert [h["step"] for h in hist] == [2, 3]
    assert all(np.isfinite(h["g_loss"]) for h in hist)


@pytest.mark.parametrize("tcfg,accum", [
    (GanTrainerConfig(global_batch=2), 1),
    (GanTrainerConfig(global_batch=2, z_seed=2**31 - 3), 1),
    (GanTrainerConfig(global_batch=4, pods_alive=1, pods_total=2), 2),
], ids=["accum1", "accum1-large-seed", "elastic-accum2"])
def test_batches_match_eager_construction(tcfg, accum):
    """The one jitted input call gives bitwise the reals and latents of the
    eager construction, at the same flat indices, shapes and dtypes."""
    tr = _trainer(tcfg)
    assert tr.accum == accum
    hw, c = TINY.out_hw(TINY.layers[-1][0]), TINY.layers[-1][2]
    for step in (0, 1, 3, 1000):
        reals, zs = tr._batches(step)
        want_reals, want_zs = _eager_inputs(tr, step)
        assert reals.shape == (accum, tr.micro, hw, hw, c)
        assert zs.shape == (accum, tr.micro, TINY.z_dim)
        assert (reals.dtype, zs.dtype) == (want_reals.dtype, want_zs.dtype)
        np.testing.assert_array_equal(np.asarray(reals),
                                      np.asarray(want_reals))
        np.testing.assert_array_equal(np.asarray(zs), np.asarray(want_zs))


def test_inputs_and_step_compile_once():
    """The step index is a traced argument: four steps leave one compiled
    entry in the input function and one in the step."""
    tr = _trainer(GanTrainerConfig(global_batch=2))
    tr.run(tr.init_state(jax.random.key(0)), steps=4)
    assert tr._inputs_fn._cache_size() == 1
    assert tr._step_fn._cache_size() == 1
