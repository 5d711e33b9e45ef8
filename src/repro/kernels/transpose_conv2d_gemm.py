"""Implicit-GEMM transpose convolution as a single Pallas TPU kernel.

The paper's kernel segregation (and both Pallas grids in
``transpose_conv2d.py``) is *spatial*: touch each output element once,
skip the structural zeros of the stride-2 upsample. For the channel-deep,
small-spatial head layers of the Table-4 generators (4x4/8x8 maps,
512–2048 channels) that framing misses where the time actually goes: the
per-phase GEMMs are skinny (``ceil(M/2)^2`` rows) and the full weight
stack is re-fetched for every batch item, so the layer is bound by weight
HBM traffic and MXU-unfriendly shapes, not by output-map stores.

This kernel takes the opposite, GANAX-style formulation (dense compute,
irregularity in *addressing*): the whole layer is ONE flat GEMM ::

    out[B*M*M, Cout] = gather[B*M*M, n*n*Cin] @ kernel[n*n*Cin, Cout]

where row ``r`` decodes to ``(b, oh, ow)`` and column ``c`` to
``(kh, kw, cin)``. The gather operand is never materialized: each grid
step reconstructs its ``(tile_m, tile_k)`` slab in VMEM with a masked
one-hot matmul against the resident input plane — the transpose-conv
predicate (tap ``(kh, kw)`` of output ``(oh, ow)`` reads input
``((oh + kh - P)/2, (ow + kw - P)/2)`` iff both are even and in range)
folds into the one-hot mask, so out-of-bound and parity-mismatched taps
contribute exact zero rows. Every MAC — the gather included — is an MXU
matmul with ``preferred_element_type=float32``.

Grid layout: ``(m_tile, cout_tile, k_step)`` with ``dimension_semantics
= (parallel, parallel, arbitrary)``; the k axis walks ``cin`` tiles
outermost and kernel taps innermost, carrying the fp32 accumulator with
the usual ``@pl.when(kk == 0)`` init, and applies the fused
:class:`~repro.kernels.epilogue.Epilogue` (``+ bias`` then activation) on
the accumulator at the LAST k step exactly like the phase-fused kernel.

Tradeoffs vs the segregated grids (see docs/ARCHITECTURE.md): the dense
GEMM executes ~4x the MACs of the segregated form (it multiplies over
the parity zeros), but batch folds into the GEMM M dimension, so the full
weight stack streams ``ceil(B*M*M / tile_m)`` times instead of once per
batch item — on batch-serving head layers that amortization dominates.
The input plane rides whole in VMEM (footprint ``B*N*N*tile_k``), which
is exactly the regime this kernel targets; spatially large layers lose
the autotune race to the spatially-tiled fused kernel long before VMEM
becomes the binding constraint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import segregation as seg
from repro.kernels import epilogue as epilib
from repro.kernels.mosaic import block_bytes, compiler_params, pipelined_bytes


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def default_gemm_tiles(
    b: int, n_in: int, n_k: int, padding: int, cin: int, cout: int
):
    """Default ``(tile_m, tile_n, tile_k)`` of the implicit-GEMM kernel.

    ``tile_m`` tiles the flattened ``B*M*M`` GEMM rows (sublane-aligned),
    ``tile_n`` the ``Cout`` lanes and ``tile_k`` the ``Cin`` half of the
    reduction. Single source of the tile-default logic — the autotuner's
    gemm roofline model imports this so its geometry can never drift from
    what the kernel runs.
    """
    m = seg.output_size(n_in, n_k, padding)
    rows = b * m * m
    tile_m = min(256, _round_up(rows, 8))
    tile_n = 128 if cout % 128 == 0 else cout
    tile_k = 512 if cin % 512 == 0 else cin
    return tile_m, tile_n, tile_k


def gemm_vmem_bytes(
    b: int, n_in: int, n_k: int, padding: int, cin: int, cout: int,
    dtype_bytes: int = 4, tiles: tuple[int, int, int] | None = None,
) -> int:
    """VMEM estimate of one grid step: the resident ``(B, N, N, tile_k)``
    input plane, the weight, bias and fp32 output blocks (all
    double-buffered), plus the ``(tile_m, B*N*N)`` one-hot gather operand
    and the gathered slab it produces. The plane and the one-hot grow with
    ``B*N*N``, which confines this kernel to the small-map head layers."""
    m = seg.output_size(n_in, n_k, padding)
    dtm, dtn, dtk = default_gemm_tiles(b, n_in, n_k, padding, cin, cout)
    tm, tn, tk = tiles or (dtm, dtn, dtk)
    tm = min(tm, _round_up(b * m * m, 8))
    return pipelined_bytes(
        ((b, n_in, n_in, tk), dtype_bytes), ((tk, tn), dtype_bytes),
        ((1, tn), 4), ((tm, tn), 4),
    ) + block_bytes((tm, b * n_in * n_in), dtype_bytes) + block_bytes(
        (tm, tk), dtype_bytes
    )


def _gemm_kernel(
    x_ref, w_ref, *rest, tm, b, n_in, m, n_k, n_tap, padding, epi
):
    """One ``(m_tile, cout_tile, k_step)`` grid step: gather the input
    slab for this (tap, cin-tile) k column block and accumulate its GEMM
    contribution.

    ``rest`` is ``(b_ref, o_ref)`` when the epilogue carries a bias and
    ``(o_ref,)`` otherwise — same convention as the phase-fused kernel.
    """
    b_ref = rest[0] if epi is not None and epi.bias else None
    o_ref = rest[-1]
    mm = pl.program_id(0)
    kk = pl.program_id(2)
    # k-step decode: taps innermost so the input-plane block index
    # (kk // n_tap) is constant across consecutive steps
    tap = kk % n_tap
    kh, kw = tap // n_k, tap % n_k

    # GEMM-row decode: r -> (batch, oh, ow); rows past B*M*M are padding
    rid = mm * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    bi = rid // (m * m)
    oh = (rid // m) % m
    ow = rid % m
    # the masked-gather predicate: output (oh, ow) under tap (kh, kw)
    # reads input ((oh+kh-P)/2, (ow+kw-P)/2) iff both are even and in
    # range — the bed-of-nails parity test, moved into addressing
    ar = oh + kh - padding
    ac = ow + kw - padding
    ih, iw = ar // 2, ac // 2
    valid = (
        (ar % 2 == 0) & (ac % 2 == 0)
        & (ar >= 0) & (ac >= 0)
        & (ih < n_in) & (iw < n_in)
        & (bi < b)
    )
    src = (
        jnp.clip(bi, 0, b - 1) * n_in + jnp.clip(ih, 0, n_in - 1)
    ) * n_in + jnp.clip(iw, 0, n_in - 1)

    plane = x_ref[...].reshape(b * n_in * n_in, x_ref.shape[-1])
    # one-hot matmul gather: invalid taps become all-zero rows, so the
    # out-of-bound mask costs nothing beyond the onehot GEMM itself
    onehot = (
        (src == jax.lax.broadcasted_iota(
            jnp.int32, (tm, b * n_in * n_in), 1))
        & valid
    ).astype(plane.dtype)
    gathered = jnp.dot(
        onehot, plane, preferred_element_type=jnp.float32
    ).astype(plane.dtype)  # exact: each row copies one input element
    acc = jnp.dot(gathered, w_ref[0], preferred_element_type=jnp.float32)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += acc

    if epi is not None:
        @pl.when(kk == pl.num_programs(2) - 1)
        def _epilogue():
            y = o_ref[...]
            if b_ref is not None:
                y = y + b_ref[0]  # (tn,) fp32, broadcast over the rows
            o_ref[...] = epi.apply_act(y)


@functools.partial(
    jax.jit,
    static_argnames=(
        "padding", "tile_m", "tile_n", "tile_k", "interpret", "epilogue",
    ),
)
def transpose_conv2d_pallas_gemm(
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    padding: int = 0,
    *,
    tile_m: int | None = None,
    tile_n: int | None = None,
    tile_k: int | None = None,
    interpret: bool | None = None,
    epilogue=None,
    bias: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Implicit-GEMM unified transpose conv (single launch).

    x: (B, N, N, Cin) NHWC; kernel: (n, n, Cin, Cout) HWIO. Returns
    (B, M, M, Cout) with M = 2N - n + 2*padding, fp32 (inputs may be
    bf16; accumulation is fp32 either way). ``tile_m`` tiles the
    flattened ``B*M*M`` GEMM rows, ``tile_n`` the output channels
    (must divide Cout), ``tile_k`` the input channels (must divide Cin).
    ``epilogue``/``bias`` behave exactly as in
    :func:`~repro.kernels.transpose_conv2d.transpose_conv2d_pallas`.
    """
    if interpret is None:  # interpret=True on CPU so tests/benches run anywhere
        interpret = jax.default_backend() == "cpu"
    epi = epilib.canonical(epilogue)
    if (epi is not None and epi.bias) != (bias is not None):
        raise ValueError(
            f"epilogue {epi.tag() if epi else None!r} and "
            f"bias={'set' if bias is not None else None} disagree"
        )
    b, n_in, _, cin = x.shape
    n_k = kernel.shape[0]
    cout = kernel.shape[3]
    m = seg.output_size(n_in, n_k, padding)
    rows = b * m * m
    n_tap = n_k * n_k

    dtm, dtn, dtk = default_gemm_tiles(b, n_in, n_k, padding, cin, cout)
    tm = min(tile_m or dtm, _round_up(rows, 8))
    tn = tile_n or dtn
    tk = tile_k or dtk
    if cout % tn or cin % tk:
        raise ValueError(f"cout={cout} % {tn} or cin={cin} % {tk} != 0")
    n_m = pl.cdiv(rows, tm)
    n_co, n_ci = cout // tn, cin // tk

    wr = kernel.reshape(n_tap, cin, cout)
    grid = (n_m, n_co, n_ci * n_tap)
    in_specs = [
        # full input plane, cin-tiled: constant across the n_tap
        # consecutive k steps that share a cin tile (taps are the fast
        # k axis), so the plane is fetched once per (m, cout, cin) block
        pl.BlockSpec(
            (b, n_in, n_in, tk),
            lambda mm, co, kk, _t=n_tap: (0, 0, 0, kk // _t),
        ),
        pl.BlockSpec(
            (1, tk, tn),
            lambda mm, co, kk, _t=n_tap: (kk % _t, kk // _t, co),
        ),
    ]
    operands = [x, wr]
    if epi is not None and epi.bias:
        # broadcast bias: ONE (1, tn) block per cout tile
        in_specs.append(pl.BlockSpec((1, tn), lambda mm, co, kk: (0, co)))
        operands.append(bias.reshape(1, cout).astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(
            _gemm_kernel, tm=tm, b=b, n_in=n_in, m=m, n_k=n_k,
            n_tap=n_tap, padding=padding, epi=epi,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tm, tn), lambda mm, co, kk: (mm, co)),
        out_shape=jax.ShapeDtypeStruct((n_m * tm, cout), jnp.float32),
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        name="tconv_gemm",
        interpret=interpret,
    )(*operands)
    return out[:rows].reshape(b, m, m, cout)
