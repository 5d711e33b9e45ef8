"""MoE: shard_map expert-parallel path vs reference path, capacity/dropping
semantics, router dtype."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.models import layers as L


def _cfg(**kw):
    return dataclasses.replace(
        reduced(get_config("dbrx-132b")), dtype="float32", **kw
    )


def test_shard_map_path_equals_reference():
    cfg = _cfg()
    p = L.moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model)) * 0.5
    ref_out, ref_aux = L.moe(p, cfg, x)  # no mesh -> reference path
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        sm_out, sm_aux = jax.jit(lambda p, x: L.moe(p, cfg, x))(p, x)
    np.testing.assert_allclose(ref_out, sm_out, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(ref_aux), float(sm_aux), rtol=1e-5)


def test_capacity_drops_tokens():
    """With capacity_factor -> 0 every token is dropped: output == shared
    expert only (zero when there is none)."""
    cfg = _cfg(moe=dataclasses.replace(
        reduced(get_config("dbrx-132b")).moe, capacity_factor=1e-9
    ))
    p = L.moe_init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (1, 16, cfg.d_model))
    out, _ = L.moe(p, cfg, x)
    # capacity=1: at most E tokens survive; most of the output rows are zero
    zero_rows = jnp.sum(jnp.all(out == 0, axis=-1))
    assert int(zero_rows) >= 16 - cfg.moe.n_experts


def test_router_weights_stay_model_dtype():
    cfg = reduced(get_config("kimi-k2-1t-a32b"))
    p = L.moe_init(jax.random.key(0), cfg)
    x = jnp.ones((1, 8, cfg.d_model), jnp.bfloat16)
    top_p, top_e, probs = L._router(p, cfg, x.reshape(8, cfg.d_model))
    assert probs.dtype == jnp.float32      # stable softmax/top-k
    assert top_e.shape == (8, cfg.moe.top_k)
    np.testing.assert_allclose(np.asarray(top_p.sum(-1)), 1.0, rtol=1e-5)


def test_shared_expert_applied():
    cfg = reduced(get_config("kimi-k2-1t-a32b"))
    assert cfg.moe.n_shared_experts == 1
    p = L.moe_init(jax.random.key(0), cfg)
    assert "shared" in p
    x = jax.random.normal(jax.random.key(1), (1, 8, cfg.d_model)).astype(
        jnp.bfloat16
    )
    out, _ = L.moe(p, cfg, x)
    assert out.shape == x.shape


def test_dispatch_combine_identity_experts():
    """If every expert is the identity (w_gate/w_up st. silu(g)*u == x is
    impossible exactly, so test zero experts): output must be exactly 0 and
    gradients finite."""
    cfg = _cfg()
    p = L.moe_init(jax.random.key(0), cfg)
    p = jax.tree_util.tree_map(jnp.zeros_like, p)
    x = jax.random.normal(jax.random.key(2), (1, 16, cfg.d_model))
    out, aux = L.moe(p, cfg, x)
    assert float(jnp.max(jnp.abs(out))) == 0.0

    g = jax.grad(lambda p: L.moe(p, cfg, x)[0].sum())(p)
    assert all(
        jnp.all(jnp.isfinite(leaf)) for leaf in jax.tree_util.tree_leaves(g)
    )
