"""Phase-fused spatially-tiled Pallas kernel: structure + numerics.

Everything runs in interpret mode on CPU (the kernel body executes in
Python), validating the exact BlockSpec/grid/halo logic that runs on real
TPUs: odd kernels, odd paddings, extents that don't divide the spatial
tiles, bf16 vs fp32 tolerances, and the custom VJP.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import transpose_conv2d as tc2d
from repro.kernels.transpose_conv2d import (
    transpose_conv2d_pallas,
    transpose_conv2d_pallas_phase,
)

RNG = np.random.default_rng(11)


def _rand(shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


@pytest.mark.parametrize("n_k", [3, 5])
@pytest.mark.parametrize("pad", [1, 3])
@pytest.mark.parametrize("n_in", [5, 12])
def test_odd_kernels_odd_paddings(n_k, pad, n_in):
    """Odd kernels exercise the zero-padded sub-kernel stack; odd paddings
    exercise the k00<->k11 role swap (paper §3.4) inside the fused kernel."""
    if 2 * n_in - n_k + 2 * pad <= 0:
        pytest.skip("empty output")
    x = _rand((2, n_in, n_in, 3))
    k = _rand((n_k, n_k, 3, 4))
    want = ref.conventional_ref(x, k, pad)
    got = transpose_conv2d_pallas(x, k, pad)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tile_h,tile_w", [(2, 3), (3, 2), (4, 8), (5, 5)])
def test_tile_sizes_that_do_not_divide(tile_h, tile_w):
    """Non-square-friendly extents: Hp=13 divides none of these tiles, so the
    last tile row/col over-computes into the zero halo and is cropped."""
    x = _rand((1, 12, 12, 2))
    k = _rand((4, 4, 2, 2))
    want = ref.conventional_ref(x, k, 1)  # m = 22 -> Hp = 11
    got = transpose_conv2d_pallas(x, k, 1, tile_h=tile_h, tile_w=tile_w)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n_in,n_k,pad", [(9, 3, 1), (7, 5, 2), (8, 5, 2)])
def test_odd_output_extents(n_in, n_k, pad):
    """Odd M: the rounded-up (Hp, 2) interleave over-computes one row/col."""
    m = 2 * n_in - n_k + 2 * pad
    assert m % 2 == 1
    x = _rand((1, n_in, n_in, 3))
    k = _rand((n_k, n_k, 3, 2))
    want = ref.conventional_ref(x, k, pad)
    got = transpose_conv2d_pallas(x, k, pad, tile_h=3, tile_w=4)
    assert got.shape == (1, m, m, 2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,tol", [
    (jnp.float32, 1e-4),
    (jnp.bfloat16, 0.07),
])
def test_dtype_tolerance_sweep(dtype, tol):
    """bf16 inputs accumulate in fp32 (preferred_element_type): the error is
    bounded by input rounding, not accumulation length."""
    x = _rand((1, 16, 16, 8)).astype(dtype)
    k = _rand((4, 4, 8, 8)).astype(dtype)
    want = ref.conventional_ref(x.astype(jnp.float32), k.astype(jnp.float32), 2)
    got = transpose_conv2d_pallas(x, k, 2, tile_h=4, tile_w=8)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_input_blockspec_is_spatially_tiled():
    """The acceptance criterion: per-grid-step input loads are halo'd spatial
    tiles, never the full (N, N) plane, and the grid walks spatial tiles."""
    captured = {}
    orig = tc2d.pl.pallas_call

    def spy(kernel, **kw):
        captured["grid"] = kw["grid"]
        captured["in_block"] = kw["in_specs"][0].block_shape
        return orig(kernel, **kw)

    tc2d.pl.pallas_call = spy
    try:
        # unique shape so jit actually retraces and the spy runs
        x = _rand((1, 48, 48, 2))
        k = _rand((4, 4, 2, 2))
        want = ref.conventional_ref(x, k, 2)
        got = transpose_conv2d_pallas(x, k, 2)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    finally:
        tc2d.pl.pallas_call = orig

    # halo'd windows are Element block dims (element offsets)
    b, th, tw, ci = (d.block_size for d in captured["in_block"])
    # N=48, P=2 -> M=96, Hp=48: default tile_h=8 -> 6 h-tiles, halo R-1=1
    assert captured["grid"][1] > 1 and captured["grid"][2] >= 1
    assert th < 48 and th <= 8 + 1 + 1  # tile + skew + halo, not the plane


def test_phase_and_fused_kernels_agree():
    x = _rand((2, 10, 10, 4))
    k = _rand((4, 4, 4, 4))
    a = transpose_conv2d_pallas(x, k, 2, tile_h=4, tile_w=4)
    b = transpose_conv2d_pallas_phase(x, k, 2)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pad", [1, 2])
def test_vjp_gradcheck_vs_unified(pad):
    """ops.transpose_conv2d_pallas (fused fwd, custom VJP) must produce the
    same gradients as differentiating transpose_conv_unified directly."""
    from repro.core.transpose_conv import transpose_conv_unified

    x = _rand((1, 7, 7, 2))
    k = _rand((3, 3, 2, 3))

    def f_pallas(x, k):
        return jnp.sum(jnp.sin(ops.transpose_conv2d_pallas(x, k, pad)))

    def f_ref(x, k):
        return jnp.sum(jnp.sin(transpose_conv_unified(x, k, pad)))

    gp = jax.grad(f_pallas, argnums=(0, 1))(x, k)
    gr = jax.grad(f_ref, argnums=(0, 1))(x, k)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_vjp_gradcheck_phase_wrapper(pad=2):
    from repro.core.transpose_conv import transpose_conv_unified

    x = _rand((1, 6, 6, 2))
    k = _rand((4, 4, 2, 2))
    gp = jax.grad(
        lambda x: jnp.sum(ops.transpose_conv2d_pallas_phase(x, k, pad) ** 2)
    )(x)
    gr = jax.grad(
        lambda x: jnp.sum(transpose_conv_unified(x, k, pad) ** 2)
    )(x)
    np.testing.assert_allclose(gp, gr, rtol=1e-4, atol=1e-4)
