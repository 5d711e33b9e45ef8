"""``correct`` on the CPU at a size a test run holds: the program passes,
and each fault a cell can have, and the control, fail.

The runs skip the harness's look for a chip and drive the rest of a run
(set-up, window, check) with channels cut and a short window."""
import contextlib
import dataclasses

import benchpath
import jax.numpy as jnp
import pytest

from harness import faults, registry, serve, train

RUN = benchpath.load("run")
BENCH = registry.load_benchmark()
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SEED = 2**31 + 101


def small(name, scale, **traffic):
    cell = registry.cell(BENCH, name)
    cfg = dict(cell.config)
    cfg["layers"] = [[hw, max(ci // scale, 2), max(co // scale, 2)]
                     for hw, ci, co in cfg["layers"]]
    return dataclasses.replace(cell, config=cfg,
                               traffic={**cell.traffic, **traffic})


@pytest.fixture(autouse=True)
def hermetic(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))


def serve_cell():
    return small("dcgan.serve.poisson", 32, rate_rps=60, check_random=6,
                 check_largest=2)


SERVE_FAULTS = {"program": contextlib.nullcontext,
                "altered": faults.serve_altered, "half": faults.serve_half}


@pytest.mark.parametrize("what", sorted(SERVE_FAULTS))
def test_serving_correct_catches_each_fault(what):
    with SERVE_FAULTS[what]():
        run = RUN.execute(serve_cell(), SEED, 0.4, False, DEVICE, 0.0)
    assert run.attempted > 0 and run.failed == 0
    assert run.correct == (what == "program"), run.checks


def serve_control(control):
    cell = serve_cell()
    run = RUN.Run(cell=cell, seed=SEED, seconds=0.4, peaks={}, device={})
    answers = serve.run_serving(run, 0.0, False)
    ref = serve.system.reference(cell.config)
    return cell, serve.compare(ref, cell.config, SEED, answers,
                               control=control)


def test_serving_control_in_bfloat16_fails_a_limit():
    cell, got = serve_control("bfloat16")
    assert got["bf16_exact_share"] == 1.0
    assert any(v > cell.config["limits"][k] for k, v in got.items()), got


def test_serving_control_with_float32_output_fails_a_limit():
    """Weights and activations in bfloat16, the output in float32: off
    bfloat16's grid, so only the gap to the reference can catch it."""
    cell, got = serve_control("bfloat16_f32_out")
    assert got["bf16_exact_share"] < 0.01
    assert any(v > cell.config["limits"][k] for k, v in got.items()), got


def train_cell():
    return small("dcgan.train.b128", 32, global_batch=8)


TRAIN_FAULTS = {"program": None, "half": faults.train_half,
                "unchanged": faults.train_unchanged}


@pytest.mark.parametrize("what", sorted(TRAIN_FAULTS))
def test_training_correct_catches_each_fault(what):
    cell = train_cell()
    run = RUN.Run(cell=cell, seed=SEED, seconds=0.01, peaks={}, device={})
    train.run_training(run, 0.0, False, TRAIN_FAULTS[what])
    assert run.steps >= 1
    assert run.correct == (what == "program"), run.checks


def train_control(**how):
    cell = train_cell()
    cfg, mix = cell.config, cell.traffic
    ref = serve.system.reference(cfg)
    hw = 2 * cfg["layers"][-1][0] - cfg["kernel"] + 2 * cfg["padding"]
    data = train.Images(SEED, mix["global_batch"], hw, cfg["layers"][-1][2])
    zseed = train.loadgen.jax_seed(SEED) >> 1
    low = train.reference_readings(ref, cfg, mix, SEED, data, zseed, **how)
    return cfg, train.compare(low, ref, cfg, mix, SEED, data, zseed)


def test_training_control_in_bfloat16_fails_a_limit():
    cfg, got = train_control(dtype=jnp.bfloat16)
    assert any(got[k] > cfg["limits"][k] for k in got), got


def test_training_control_in_mixed_precision_fails_a_limit():
    """Float32 weights and losses with the passes in bfloat16."""
    cfg, got = train_control(compute=jnp.bfloat16)
    assert any(got[k] > cfg["limits"][k] for k in got), got
