"""Segregated Pallas backward (dx + dw): structure + grad numerics.

Everything runs in interpret mode on CPU (the kernel bodies execute in
Python), validating the exact BlockSpec/grid/halo logic that runs on real
TPUs against the lax VJP of ``transpose_conv_unified`` — the same sweep the
forward suite (tests/test_fused_kernel.py) uses: odd kernels, odd paddings,
odd output extents, tiles that don't divide, bf16 vs fp32 tolerances — plus
``jax.grad`` through the custom-VJP ops layer and a small DCGAN loss.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.transpose_conv import transpose_conv_unified
from repro.kernels import ops
from repro.kernels import transpose_conv2d_bwd as tcb
from repro.kernels.transpose_conv2d_bwd import (
    transpose_conv2d_bwd_pallas,
    transpose_conv2d_dx_pallas,
)

RNG = np.random.default_rng(7)


def _rand(shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


def _lax_grads(x, k, g, pad):
    _, vjp = jax.vjp(lambda a, b: transpose_conv_unified(a, b, pad), x, k)
    return vjp(g)


def _shapes(n_in, n_k, pad, cin, cout, b=1):
    m = 2 * n_in - n_k + 2 * pad
    x = _rand((b, n_in, n_in, cin))
    k = _rand((n_k, n_k, cin, cout))
    g = _rand((b, m, m, cout))
    return x, k, g


@pytest.mark.parametrize("n_k", [3, 5])
@pytest.mark.parametrize("pad", [1, 3])
@pytest.mark.parametrize("n_in", [5, 12])
def test_odd_kernels_odd_paddings(n_k, pad, n_in):
    """Odd kernels exercise the zero-padded sub-kernel stack (whose garbage
    taps must be sliced away from dw); odd paddings exercise the k00<->k11
    role swap (paper §3.4) in both gradients."""
    if 2 * n_in - n_k + 2 * pad <= 0:
        pytest.skip("empty output")
    x, k, g = _shapes(n_in, n_k, pad, 3, 4, b=2)
    dx_ref, dw_ref = _lax_grads(x, k, g, pad)
    dx, dw = transpose_conv2d_bwd_pallas(x, k, g, pad)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dw, dw_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("pad", [0, 2])
def test_even_kernel_gan_paddings(pad):
    """4x4 kernels (every Table-4 GAN layer); pad=0 exercises the negative
    phase-offset path of the dx plane shift."""
    x, k, g = _shapes(6, 4, pad, 2, 3, b=2)
    dx_ref, dw_ref = _lax_grads(x, k, g, pad)
    dx, dw = transpose_conv2d_bwd_pallas(x, k, g, pad)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dw, dw_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n_in,n_k,pad", [(9, 3, 1), (7, 5, 2), (8, 5, 2)])
def test_odd_output_extents(n_in, n_k, pad):
    """Odd M: the parity planes have unequal extents; the missing last
    row/col is zero-padded and must contribute nothing to either gradient."""
    m = 2 * n_in - n_k + 2 * pad
    assert m % 2 == 1
    x, k, g = _shapes(n_in, n_k, pad, 3, 2)
    dx_ref, dw_ref = _lax_grads(x, k, g, pad)
    dx, dw = transpose_conv2d_bwd_pallas(x, k, g, pad, tile_h=3, tile_w=4)
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dw, dw_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tile_h,tile_w", [(2, 3), (3, 2), (5, 5)])
def test_tile_sizes_that_do_not_divide(tile_h, tile_w):
    """N=12 divides none of these dx tiles: the last tile row/col
    over-computes into the zero-shifted plane halo and is cropped."""
    x, k, g = _shapes(12, 4, 1, 2, 2)
    dx_ref, dw_ref = _lax_grads(x, k, g, 1)
    dx, dw = transpose_conv2d_bwd_pallas(
        x, k, g, 1, tile_h=tile_h, tile_w=tile_w, dw_tile_h=3
    )
    np.testing.assert_allclose(dx, dx_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(dw, dw_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, 1e-4),
    (jnp.bfloat16, 0.1),
])
def test_dtype_tolerance_sweep(dtype, tol):
    """bf16 primals: the cotangent is cast to the primal dtype on the host
    (bf16 MXU taps) but accumulation stays fp32 — error bounded by input
    rounding, not reduction length."""
    x, k, g = _shapes(16, 4, 2, 8, 8)
    dx_ref, dw_ref = _lax_grads(x, k, g, 2)  # fp32 reference
    dx, dw = transpose_conv2d_bwd_pallas(
        x.astype(dtype), k.astype(dtype), g, 2
    )
    assert dx.dtype == jnp.float32 and dw.dtype == jnp.float32
    np.testing.assert_allclose(dx, dx_ref, rtol=tol, atol=tol)
    np.testing.assert_allclose(dw, dw_ref, rtol=tol, atol=tol)


def test_dx_blockspec_is_spatially_tiled():
    """The dx kernel's per-grid-step load is a halo'd tile of the parity
    planes, never a full plane, and the grid walks spatial tiles."""
    captured = {}
    orig = tcb.pl.pallas_call

    def spy(kernel, **kw):
        captured["grid"] = kw["grid"]
        captured["in_block"] = kw["in_specs"][0].block_shape
        return orig(kernel, **kw)

    tcb.pl.pallas_call = spy
    try:
        x, k, g = _shapes(48, 4, 2, 2, 2)
        dx_ref, _ = _lax_grads(x, k, g, 2)
        dx = transpose_conv2d_dx_pallas(g, k, 48, 2)
        np.testing.assert_allclose(dx, dx_ref, rtol=1e-4, atol=1e-4)
    finally:
        tcb.pl.pallas_call = orig

    # halo'd windows are Element block dims (element offsets)
    ph, b, th, tw, co = (d.block_size for d in captured["in_block"])
    # N=48: default tile_h=8, halo R-1=1 -> 9-row tiles of all 4 planes
    assert ph == 4 and captured["grid"][1] > 1
    assert th < 48 and th <= 8 + 1  # tile + halo, not the plane


@pytest.mark.parametrize("pad", [1, 2])
def test_ops_grad_pallas_matches_lax(pad):
    """jax.grad through the custom-VJP ops layer: bwd="pallas" and
    bwd="lax" must agree (and match differentiating the lax unified
    implementation directly)."""
    x = _rand((1, 7, 7, 2))
    k = _rand((3, 3, 2, 3))

    def f(bwd):
        return lambda x, k: jnp.sum(
            jnp.sin(ops.transpose_conv2d_pallas(x, k, pad, None, None, bwd))
        )

    gp = jax.grad(f("pallas"), argnums=(0, 1))(x, k)
    gl = jax.grad(f("lax"), argnums=(0, 1))(x, k)
    gr = jax.grad(
        lambda x, k: jnp.sum(jnp.sin(transpose_conv_unified(x, k, pad))),
        argnums=(0, 1),
    )(x, k)
    for a, b, c in zip(gp, gl, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-4)


def test_ops_phase_wrapper_dispatches_pallas_bwd(pad=2):
    x = _rand((1, 6, 6, 2))
    k = _rand((4, 4, 2, 2))
    gp = jax.grad(
        lambda x: jnp.sum(
            ops.transpose_conv2d_pallas_phase(x, k, pad, "pallas") ** 2
        )
    )(x)
    gr = jax.grad(
        lambda x: jnp.sum(transpose_conv_unified(x, k, pad) ** 2)
    )(x)
    np.testing.assert_allclose(gp, gr, rtol=1e-4, atol=1e-4)


def test_unknown_bwd_method_raises():
    """A typo'd bwd selector must fail loudly, not silently run the lax
    fallback while the caller attributes the numbers to Pallas."""
    x = _rand((1, 6, 6, 2))
    k = _rand((4, 4, 2, 2))
    with pytest.raises(ValueError, match="unknown bwd"):
        jax.grad(
            lambda x: jnp.sum(
                ops.transpose_conv2d_pallas(x, k, 2, None, None, "Pallas")
            )
        )(x)


def test_lax_vjp_closure_is_cached():
    """The lax fallback must not re-trace jax.vjp per backward call: the
    jitted closure is built once per (padding, shapes, dtypes)."""
    ops._unified_vjp_fn.cache_clear()
    x = _rand((1, 6, 6, 2))
    k = _rand((4, 4, 2, 2))
    g = _rand((1, 12, 12, 2))
    ops._lax_bwd(2, (x, k, None, None), g)
    ops._lax_bwd(2, (x, k, None, None), g)
    info = ops._unified_vjp_fn.cache_info()
    assert info.misses == 1 and info.hits >= 1


def test_grad_through_dcgan_loss(tmp_path, monkeypatch):
    """jax.grad through a small DCGAN generator loss with every tconv layer
    forced onto the Pallas forward AND the Pallas backward (via tuned bwd
    cache entries) must match the unified-lax generator's gradients."""
    from repro.kernels import autotune
    from repro.models import gan

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.clear_cache(memory_only=True)
    cfg = dataclasses.replace(
        gan.DCGAN, layers=((4, 8, 8), (8, 8, 4))
    )
    params = gan.generator_init(jax.random.key(0), cfg)
    z = jax.random.normal(jax.random.key(1), (2, cfg.z_dim))
    for hw, cin, cout in cfg.layers:
        autotune.record(
            autotune.layer_key(2, hw, cfg.kernel, cin, cout, cfg.padding),
            {"method": "pallas", "time_s": 0.0, "source": "test"},
            direction="bwd",
        )

    def loss(params, method):
        img = gan.generator_apply(params, cfg, z, method=method)
        return jnp.mean(img ** 2)

    gp = jax.grad(lambda p: loss(p, "pallas"))(params)
    gr = jax.grad(lambda p: loss(p, "unified"))(params)
    flat_p, _ = jax.tree_util.tree_flatten(gp)
    flat_r, _ = jax.tree_util.tree_flatten(gr)
    for a, b in zip(flat_p, flat_r):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    autotune.clear_cache(memory_only=True)
