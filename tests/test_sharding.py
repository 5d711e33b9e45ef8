"""Sharding rules: param specs, divisibility filtering, constrain no-op."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as sh
from repro.models.lm import build_model


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = sh.constrain(x, "data", None)
    assert (y == x).all()


def _with_mesh(fn):
    """Run fn with a fake 16x16 production mesh visible to the rule engine
    (set_mesh requires real devices; the rules only read names/sizes)."""
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    orig = getattr(jax.sharding, "get_abstract_mesh", None)
    jax.sharding.get_abstract_mesh = lambda: mesh
    try:
        return fn()
    finally:
        if orig is None:
            del jax.sharding.get_abstract_mesh
        else:
            jax.sharding.get_abstract_mesh = orig


def test_param_specs_llama3():
    cfg = get_config("llama3-8b")
    params = build_model(cfg).abstract_params()

    def check():
        specs = sh.param_specs(params, cfg.fsdp)
        # embedding vocab-parallel with stacked-layer-free rank
        assert specs["embed"]["w"] == P("model", None)
        l0 = specs["layers"][0]
        # stacked (n_periods, d, H*hd): leading None + column-parallel
        assert l0["mixer"]["attn"]["wq"]["w"] == P(None, None, "model")
        assert l0["mixer"]["attn"]["wo"]["w"] == P(None, "model", None)
        assert l0["ffn"]["w_gate"]["w"] == P(None, None, "model")
        assert l0["ffn"]["w_down"]["w"] == P(None, "model", None)
        # norms replicated
        assert l0["mixer_norm"]["scale"] in (P(), P(None))
    _with_mesh(check)


def test_param_specs_drop_nondivisible():
    cfg = get_config("xlstm-125m")
    params = build_model(cfg).abstract_params()

    def check():
        specs = sh.param_specs(params, False)
        # w_if: (periods, d, 2*nh)=(...,8): 8 % 16 != 0 -> axis dropped
        assert specs["layers"][0]["mixer"]["mlstm"]["w_if"] in (
            P(), P(None, None, None)
        )
    _with_mesh(check)


def test_fsdp_adds_data_axis():
    cfg = get_config("dbrx-132b")
    params = build_model(cfg).abstract_params()

    def check():
        specs = sh.param_specs(params, True)
        l0 = specs["layers"][0]
        assert l0["ffn"]["experts"]["w_gate"] == P(None, "model", "data", None)
    _with_mesh(check)


def test_filter_divisibility():
    def check():
        assert sh._filter(P("model"), (32,)) == P("model")
        assert sh._filter(P("model"), (8,)) is None
        assert sh._filter(P(("data", "model")), (256,)) == P(("data", "model"))
        assert sh._filter(P("nope", "model"), (4, 32)) == P(None, "model")
    _with_mesh(check)
