"""Unified kernel-segregated transpose convolution as Pallas TPU kernels.

TPU adaptation of the paper's CUDA mechanism (DESIGN.md §2): the runtime
per-thread sub-kernel selection (``r = i%2, s = j%2``) is resolved at compile
time. Two kernels live here:

* :func:`transpose_conv2d_pallas` — the **phase-fused, spatially-tiled**
  kernel (primary). One grid step loads ONE spatial input tile (with halo)
  into VMEM and computes ALL FOUR phase accumulations from it.
* :func:`transpose_conv2d_pallas_phase` — the earlier per-phase grid
  (``phase`` as a grid axis), kept as the autotuner's baseline candidate.

Fused grid layout
-----------------

The grid is ``(batch, h_tile, w_tile, cout_tile, cin_tile)`` with
``dimension_semantics = (parallel, parallel, parallel, parallel, arbitrary)``
— only the innermost ``cin`` axis carries a loop dependency (it revisits the
same output block with a ``@pl.when(ci == 0)`` init, so it must run in order).

Input tiling + halo math: the four phases of the segregated transpose conv
read the padded input at per-parity origins ``row0(pr), col0(pc)`` (see
:func:`repro.core.segregation.plan_phases`); output phase-plane coordinates
``t ∈ [0, Hp)`` are tiled by ``tile_h``. Grid step ``(b, i, j, co, ci)``
therefore needs padded-input rows::

    [min_row0 + i*tile_h,  max_row0 + i*tile_h + tile_h + R - 2]

i.e. an input tile of ``tile_h + dr + (R - 1)`` rows where
``dr = max_row0 - min_row0 ∈ {0, 1}`` is the cross-phase origin skew and
``R - 1`` is the sub-kernel halo (``R = ceil(n/2)``). Consecutive spatial
tiles *overlap* by the halo — expressed with an all-**Element** input
BlockSpec whose index map returns element offsets
``(b, min_row0 + i*tile_h, ...)`` (:mod:`repro.kernels.mosaic`).
Per grid step the input load is the halo'd tile only — never the full
``(N, N)`` plane — so VMEM stays bounded in ``N`` and each input element is
loaded once for all four phases: 4x the arithmetic intensity of the
per-phase kernel's loads.

The four sub-kernels are zero-padded to the common ``R x R`` shape and
stacked to ``(4, R, R, Cin, Cout)``; the whole stack rides in VMEM and the
output-parity -> sub-kernel selection (including the odd-padding swap,
paper §3.4) is a static Python index into it. The output block is the
``(1, 2, 2, tile_h, tile_w, ct)`` slab of the ``(B, 2, 2, Hp, Wp, Cout)``
parity-plane layout: each plane is a dense ``(rows, cols, C)`` block that
Mosaic tiles without padding, and one XLA transpose interleaves the planes
(:func:`interleave_planes`) — the upsampled bed-of-nails buffer is never
materialized.

Inputs may be ``bf16`` (or ``fp32``); every tap is an MXU matmul with
``preferred_element_type=float32``, so accumulation is always fp32.

Both kernels are validated on CPU in interpret mode against
:mod:`repro.kernels.ref` across shape/dtype/padding sweeps (tests/).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import segregation as seg
from repro.kernels import epilogue as epilib
from repro.kernels.mosaic import (
    compiler_params, halo_block, pipelined_bytes, tile_offset,
)


def _phase_offsets(n_in: int, n_k: int, padding: int):
    """Per-output-parity padded-input origins + the fused tile geometry.

    Returns ``(row0s, col0s, pad_lo)`` where ``row0s[pr]`` is the first
    padded-input row phase ``pr`` reads (likewise cols).
    """
    plans, pad_lo, _ = seg.plan_phases(n_in, n_k, padding)
    row0s = (plans[0].row0, plans[2].row0)  # by output row parity
    col0s = (plans[0].col0, plans[1].col0)  # by output col parity
    return row0s, col0s, pad_lo


def interleave_planes(out):
    """(B, 2, 2, Hp, Wp, C) parity planes -> (B, 2Hp, 2Wp, C) output map.

    The kernels write each output-parity plane as a dense (rows, cols, C)
    block, the layout Mosaic tiles well; the stride-2 interleave is one XLA
    transpose here.
    """
    b, _, _, hp, wp, c = out.shape
    return out.transpose(0, 3, 1, 4, 2, 5).reshape(b, 2 * hp, 2 * wp, c)


def default_tiles(n_in: int, n_k: int, padding: int, cin: int, cout: int):
    """Default (tile_h, tile_w, cout_tile, cin_tile) of the fused kernel.

    The single source of the tile-default logic — the autotuner's roofline
    model (repro.kernels.autotune) imports this so its geometry can never
    drift from what the kernel actually runs.
    """
    m = seg.output_size(n_in, n_k, padding)
    hp = (m + 1) // 2
    return min(hp, 8), min(hp, 128), min(cout, 128), min(cin, 512)


def _fused_kernel(x_ref, w_ref, *rest, R, th, tw, roffs, coffs, wsels, epi):
    """One (batch, h_tile, w_tile, cout_tile, cin_tile) grid step: all four
    phase accumulations from a single halo'd input tile.

    ``rest`` is ``(b_ref, o_ref)`` when the epilogue carries a bias (the
    bias BlockSpec is broadcast: its index map depends on the cout grid axis
    only) and ``(o_ref,)`` otherwise. The epilogue — ``+ bias`` then the
    activation — is applied on the fp32 accumulator at the LAST cin step,
    before the block leaves VMEM: the output map is still touched exactly
    once in HBM.
    """
    b_ref = rest[0] if epi is not None and epi.bias else None
    o_ref = rest[-1]
    ci = pl.program_id(4)
    x = x_ref[0]  # (th + dr + R - 1, tw + dc + R - 1, ci) VMEM tile
    ct = o_ref.shape[-1]

    planes = []
    for pr in range(2):
        for pc in range(2):
            r0, c0 = roffs[pr], coffs[pc]  # static tile-local origin
            wk = w_ref[wsels[2 * pr + pc]]  # (R, R, ci, ct) sub-kernel
            acc = jnp.zeros((th * tw, ct), jnp.float32)
            for p in range(R):
                for q in range(R):
                    window = x[
                        r0 + p : r0 + p + th, c0 + q : c0 + q + tw, :
                    ].reshape(th * tw, -1)
                    acc += jnp.dot(
                        window, wk[p, q], preferred_element_type=jnp.float32
                    )
            planes.append(acc.reshape(th, tw, ct))

    @pl.when(ci == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    for ph, plane in enumerate(planes):  # block (1, 2, 2, th, tw, ct)
        o_ref[0, ph // 2, ph % 2] += plane

    if epi is not None:
        @pl.when(ci == pl.num_programs(4) - 1)
        def _epilogue():
            y = o_ref[...]
            if b_ref is not None:
                y = y + b_ref[0]  # (ct,) fp32, broadcast over the block
            o_ref[...] = epi.apply_act(y)


@functools.partial(
    jax.jit,
    static_argnames=(
        "padding", "tile_h", "tile_w", "cout_tile", "cin_tile", "interpret",
        "epilogue",
    ),
)
def transpose_conv2d_pallas(
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    padding: int = 0,
    *,
    tile_h: int | None = None,
    tile_w: int | None = None,
    cout_tile: int | None = None,
    cin_tile: int | None = None,
    interpret: bool | None = None,
    epilogue=None,
    bias: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Phase-fused, spatially-tiled unified transpose conv (single launch).

    x: (B, N, N, Cin) NHWC; kernel: (n, n, Cin, Cout) HWIO. Returns
    (B, M, M, Cout) with M = 2N - n + 2*padding, fp32 (inputs may be bf16;
    accumulation is fp32 either way). ``epilogue`` (an
    :class:`repro.kernels.epilogue.Epilogue`, static) fuses ``+ bias`` and
    the activation onto the fp32 accumulator before the single store —
    ``bias`` is the (Cout,) vector, required iff ``epilogue.bias``.
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
    R = seg.ceil_half(n_k)
    Hp = Wp = (m + 1) // 2

    row0s, col0s, pad_lo = _phase_offsets(n_in, n_k, padding)
    base_r, base_c = min(row0s), min(col0s)
    dr, dc = max(row0s) - base_r, max(col0s) - base_c  # cross-phase skew

    dth, dtw, dct, dci = default_tiles(n_in, n_k, padding, cin, cout)
    th = min(tile_h or dth, Hp)
    tw = min(tile_w or dtw, Wp)
    n_h, n_w = pl.cdiv(Hp, th), pl.cdiv(Wp, tw)
    hp, wp = n_h * th, n_w * tw  # rounded-up tiled extents

    # pad so every tile's halo'd window is in-bounds (over-computed rows/cols
    # read zeros and are cropped after the interleave reshape)
    need_r = max(row0s) + hp + R - 1
    need_c = max(col0s) + wp + R - 1
    pad_hi_r = max(0, need_r - (n_in + pad_lo))
    pad_hi_c = max(0, need_c - (n_in + pad_lo))
    xp = jnp.pad(x, ((0, 0), (pad_lo, pad_hi_r), (pad_lo, pad_hi_c), (0, 0)))

    w = seg.stack_subkernels(kernel)  # (4, R, R, Cin, Cout)
    ct = cout_tile or dct
    ci = cin_tile or dci
    if cout % ct or cin % ci:
        raise ValueError(f"cout={cout} % {ct} or cin={cin} % {ci} != 0")

    # output parity -> stacked sub-kernel index (odd padding swaps roles)
    wsels = tuple(
        2 * ((pr + padding) % 2) + ((pc + padding) % 2)
        for pr in range(2) for pc in range(2)
    )
    n_ci = cin // ci
    grid = (b, n_h, n_w, cout // ct, n_ci)
    in_specs = [
        # halo'd spatial tile: overlapping windows -> Element block dims
        # (the index map returns ELEMENT offsets, not block indices)
        pl.BlockSpec(
            halo_block(1, th + dr + R - 1, tw + dc + R - 1, ci),
            lambda bb, ih, iw, co, cc: (
                bb, base_r + ih * th, base_c + tile_offset(iw, tw, n_w),
                tile_offset(cc, ci, n_ci),
            ),
        ),
        pl.BlockSpec(
            (4, R, R, ci, ct),
            lambda bb, ih, iw, co, cc: (0, 0, 0, cc, co),
        ),
    ]
    operands = [xp, w]
    if epi is not None and epi.bias:
        # broadcast bias: ONE (1, ct) block per cout tile — the index map
        # ignores the batch/spatial/cin grid axes, so the vector is never
        # re-tiled per grid step
        in_specs.append(
            pl.BlockSpec((1, ct), lambda bb, ih, iw, co, cc: (0, co))
        )
        operands.append(bias.reshape(1, cout).astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(
            _fused_kernel, R=R, th=th, tw=tw,
            roffs=tuple(r - base_r for r in row0s),
            coffs=tuple(c - base_c for c in col0s),
            wsels=wsels, epi=epi,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 2, 2, th, tw, ct),
            lambda bb, ih, iw, co, cc: (bb, 0, 0, ih, iw, co),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 2, 2, hp, wp, cout), jnp.float32),
        compiler_params=compiler_params(
            "parallel", "parallel", "parallel", "parallel", "arbitrary",
        ),
        name="tconv_fused",
        interpret=interpret,
    )(*operands)
    return interleave_planes(out)[:, :m, :m, :]


# --------------------------------------------------------------------------
# Legacy per-phase kernel (phase as a grid axis). Each grid step reloads the
# full spatial plane and computes ONE phase — 4x the input HBM traffic of the
# fused kernel and VMEM unbounded in N. Kept as the autotuner's baseline
# candidate ("pallas_phase") and as the perf reference for benchmarks.
# --------------------------------------------------------------------------

def _phase_kernel(x_ref, w_ref, *rest, R, Hp, Wp, row0s, col0s, epi):
    """One (batch, phase, cout-tile, cin-tile) grid step."""
    b_ref = rest[0] if epi is not None and epi.bias else None
    o_ref = rest[-1]
    ph = pl.program_id(1)
    ci = pl.program_id(3)
    ct = o_ref.shape[-1]

    @pl.when(ci == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Mosaic has no dynamic_slice: one static-origin branch per phase, the
    # grid's phase index picks which one runs
    for pr in range(2):
        for pc in range(2):
            @pl.when(ph == 2 * pr + pc)
            def _accumulate(row0=row0s[pr], col0=col0s[pc]):
                x = x_ref[0]  # (Np, Np, Ci) VMEM tile
                acc = jnp.zeros((Hp * Wp, ct), jnp.float32)
                for p in range(R):
                    for q in range(R):
                        window = x[
                            row0 + p : row0 + p + Hp,
                            col0 + q : col0 + q + Wp, :,
                        ].reshape(Hp * Wp, -1)
                        acc += jnp.dot(
                            window, w_ref[0, p, q],
                            preferred_element_type=jnp.float32,
                        )
                o_ref[...] += acc.reshape(1, 1, 1, Hp, Wp, ct)

    if epi is not None:
        @pl.when(ci == pl.num_programs(3) - 1)
        def _epilogue():
            y = o_ref[...]
            if b_ref is not None:
                y = y + b_ref[0]
            o_ref[...] = epi.apply_act(y)


def phase_vmem_bytes(n_in: int, n_k: int, padding: int, cin: int,
                     cout: int, dtype_bytes: int = 4) -> int:
    """VMEM estimate of one per-phase grid step at the default channel
    tiles: the whole padded input plane, one sub-kernel block, the bias
    block and the fp32 output plane, all double-buffered."""
    m = seg.output_size(n_in, n_k, padding)
    R = seg.ceil_half(n_k)
    hp = (m + 1) // 2
    row0s, col0s, pad_lo = _phase_offsets(n_in, n_k, padding)
    np_ = max(n_in + pad_lo, max(row0s + col0s) + hp + R - 1)
    ci, ct = min(cin, 512), min(cout, 128)
    return pipelined_bytes(
        ((np_, np_, ci), dtype_bytes), ((R, R, ci, ct), dtype_bytes),
        ((1, ct), 4), ((hp, hp, ct), 4),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "padding", "cout_tile", "cin_tile", "interpret", "epilogue",
    ),
)
def transpose_conv2d_pallas_phase(
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    padding: int = 0,
    *,
    cout_tile: int | None = None,
    cin_tile: int | None = None,
    interpret: bool | None = None,
    epilogue=None,
    bias: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Per-phase unified kernel-segregated transpose conv (legacy grid).

    Takes the same fused ``epilogue``/``bias`` as the fused kernel (parity:
    both Pallas forwards execute whole layers, so the autotuner races them
    on equal terms).
    """
    if interpret is None:
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
    R = seg.ceil_half(n_k)
    Hp = Wp = (m + 1) // 2

    row0s, col0s, pad_lo = _phase_offsets(n_in, n_k, padding)
    # high-side pad so every phase's uniform (Hp + R - 1) window is in-bounds
    need = max(r0 for r0 in row0s + col0s) + Hp + R - 1
    pad_hi = max(0, need - (n_in + pad_lo))
    xp = jnp.pad(x, ((0, 0), (pad_lo, pad_hi), (pad_lo, pad_hi), (0, 0)))
    np_ = xp.shape[1]

    w = seg.stack_subkernels(kernel)  # (4, R, R, Cin, Cout)
    ct = cout_tile or min(cout, 128)
    ci = cin_tile or min(cin, 512)
    if cout % ct or cin % ci:
        raise ValueError(f"cout={cout} % {ct} or cin={cin} % {ci} != 0")

    grid = (b, 4, cout // ct, cin // ci)
    in_specs = [
        pl.BlockSpec(
            (1, np_, np_, ci), lambda bb, ph, co, cc: (bb, 0, 0, cc)
        ),
        pl.BlockSpec(
            (1, R, R, ci, ct),
            # the paper's "runtime sub-kernel selection": phase parity
            # (+ odd-padding swap) picks the stacked sub-kernel block
            lambda bb, ph, co, cc, _p=padding: (
                ((ph // 2 + _p) % 2) * 2 + (ph % 2 + _p) % 2, 0, 0, cc, co
            ),
        ),
    ]
    operands = [xp, w]
    if epi is not None and epi.bias:
        in_specs.append(
            pl.BlockSpec((1, ct), lambda bb, ph, co, cc: (0, co))
        )
        operands.append(bias.reshape(1, cout).astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(
            _phase_kernel, R=R, Hp=Hp, Wp=Wp, row0s=row0s, col0s=col0s,
            epi=epi,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, 1, Hp, Wp, ct),
            lambda bb, ph, co, cc: (bb, ph // 2, ph % 2, 0, 0, co),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 2, 2, Hp, Wp, cout), jnp.float32),
        compiler_params=compiler_params(
            "parallel", "parallel", "parallel", "arbitrary",
        ),
        name="tconv_phase",
        interpret=interpret,
    )(*operands)
    return interleave_planes(out)[:, :m, :m, :]
