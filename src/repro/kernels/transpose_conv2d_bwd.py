"""Phase-segregated Pallas backward pass for the unified transpose conv.

The segregation mechanism of the paper applies symmetrically to gradients:
the cotangent ``g`` of the forward output decomposes into the same four
output-parity planes ``g_{pr,pc}[t, s] = g[2t + pr, 2s + pc]`` the fused
forward kernel writes, so both gradients untangle into dense stride-1
correlations (GANAX keeps deconvolution dense on both passes the same way):

dx — *input gradient* (one kernel, :func:`transpose_conv2d_dx_pallas`)::

    dx[i, j, ci] = sum_{pr,pc} sum_{p,q}
        g_{pr,pc}[i + offr(pr) - p, j + offc(pc) - q, co]
        * k_{sel(pr,pc)}[p, q, ci, co]

  with ``offr(pr) = pad_lo - row0(pr)`` (from
  :func:`repro.core.segregation.plan_phases` — the transpose of the
  forward's per-phase read origins) and ``sel`` the forward's output-parity
  -> sub-kernel selection (odd-padding swap included). The ``- p`` makes
  each term a correlation with the *flipped* sub-kernel; the flip is folded
  into the static tap origin ``R - 1 - p`` inside the kernel. Each grid step
  ``(b, i_tile, j_tile, cin_tile, cout_tile)`` loads ONE halo'd tile of all
  four parity planes (the planes are pre-shifted on the host so every phase
  reads at the same tile-local origin) and computes ALL FOUR correlations
  from it — the same one-load-serves-four-phases discipline as the fused
  forward. The innermost ``cout`` axis is the contraction and carries the
  ``@pl.when(co == 0)`` accumulator init.

dw — *weight gradient* (one kernel, :func:`transpose_conv2d_dw_pallas`)::

    dk_{sel(pr,pc)}[p, q, ci, co] = sum_{b,t,s}
        Ipad[b, row0(pr) + t + p, col0(pc) + s + q, ci]
        * g_{pr,pc}[b, t, s, co]

  a per-parity reduction over batch x space into the stacked
  ``(4, R, R, Cin, Cout)`` sub-kernel gradient. The grid is
  ``(cin_tile, cout_tile, batch, h_tile)`` with the trailing two axes
  ``arbitrary``: the output block is a grid-carried fp32 accumulator
  revisited across every ``(batch, h_tile)`` step. Each step loads the same
  halo'd input tile the forward uses plus the four (zero-padded-to-uniform)
  parity-plane tiles of ``g``, and every ``(phase, p, q)`` tap is one MXU
  ``dot_general`` contracting the ``tile_h * Wp`` spatial axis.

Both kernels take bf16 inputs (the cotangent is cast to the primal dtype on
the host) and accumulate in fp32 via ``preferred_element_type`` — the
bf16-in/fp32-accum discipline of the forward. Both are validated on CPU in
interpret mode against the lax VJP of ``transpose_conv_unified``
(tests/test_bwd_kernel.py).

Fused-epilogue layers (``act(tconv + b)``, :mod:`repro.kernels.epilogue`)
enter through :func:`transpose_conv2d_bwd_pallas` with the epilogue and the
saved forward output ``y``: a small fused Pallas prologue
(:func:`epilogue_grad_pallas`) computes the masked cotangent
``gm = g · act'(y)`` in one elementwise pass (``act'`` is never
materialized separately), the dx/dw kernels consume the pre-masked ``gm``
unchanged, and the dw grid's second grid-carried accumulator reduces the
bias gradient ``db = Σ_{b,space} gm`` in the same launch
(``with_db=True`` — the parity-plane tiles are already in VMEM, so db is
HBM-free).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core import segregation as seg
from repro.kernels import epilogue as epilib
from repro.kernels.mosaic import compiler_params, halo_block, tile_offset
from repro.kernels.transpose_conv2d import _phase_offsets


def _wsels(padding: int):
    """Output parity -> stacked sub-kernel index (odd-padding swap, §3.4)."""
    return tuple(
        2 * ((pr + padding) % 2) + ((pc + padding) % 2)
        for pr in range(2) for pc in range(2)
    )


def _parity_planes(g):
    """(B, M, M, C) cotangent -> (4, B, Hp, Wp, C) output-parity planes.

    Odd ``M`` pads the missing last row/col with zeros (zero cotangent
    contributes zero to either gradient).
    """
    b, m, _, c = g.shape
    hp, wp = (m + 1) // 2, (m + 1) // 2
    g2 = jnp.pad(g, ((0, 0), (0, 2 * hp - m), (0, 2 * wp - m), (0, 0)))
    g6 = g2.reshape(b, hp, 2, wp, 2, c)
    return jnp.stack(
        [g6[:, :, pr, :, pc, :] for pr in range(2) for pc in range(2)]
    )


def _place(a, axis, lo: int, size: int):
    """Shift+fit along ``axis``: result[r] = a[r - lo], zero outside, extent
    ``size``. Negative ``lo`` crops the head (those rows are never read)."""
    if lo < 0:
        a = lax.slice_in_dim(a, -lo, a.shape[axis], axis=axis)
    elif lo > 0:
        pads = [(0, 0)] * a.ndim
        pads[axis] = (lo, 0)
        a = jnp.pad(a, pads)
    cur = a.shape[axis]
    if cur < size:
        pads = [(0, 0)] * a.ndim
        pads[axis] = (0, size - cur)
        a = jnp.pad(a, pads)
    elif cur > size:
        a = lax.slice_in_dim(a, 0, size, axis=axis)
    return a


def default_bwd_tiles(n_in: int, n_k: int, padding: int, cin: int, cout: int):
    """Default (tile_h, tile_w, cin_tile, cout_tile) of the dx kernel.

    Mirrors the forward's ``default_tiles`` with the channel roles swapped:
    dx tiles its (N, N, Cin) output spatially and by ``cin``, and reduces
    over ``cout``. The autotuner's bwd roofline model imports this so its
    geometry can never drift from what the kernel runs.
    """
    return min(n_in, 8), min(n_in, 128), min(cin, 128), min(cout, 512)


def default_dw_tile(n_in: int, n_k: int, padding: int) -> int:
    """Default phase-plane row tile of the dw reduction kernel."""
    m = seg.output_size(n_in, n_k, padding)
    return min((m + 1) // 2, 8)


# ------------------------------------------------------- epilogue prologue

def _epilogue_grad_kernel(g_ref, y_ref, o_ref, *, epi):
    """One (batch, row_tile) grid step: ``gm = g * act'(y)`` elementwise,
    in fp32 (the v5e vector unit has no bf16 compare)."""
    gm = epi.grad_from_y(g_ref[...].astype(jnp.float32),
                         y_ref[...].astype(jnp.float32))
    o_ref[...] = gm.astype(o_ref.dtype)


def epilogue_grad_pallas(
    g: jnp.ndarray,
    y: jnp.ndarray,
    epilogue,
    *,
    tile_m: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused backward prologue of the layer epilogue: ``g · act'(y)``.

    ``g`` is the cotangent of the POST-activation output; ``y`` the saved
    forward output (the residual — the VJP saves ``y`` instead of
    recomputing the pre-activation, see :mod:`repro.kernels.epilogue`).
    One fused elementwise pass: ``act'`` is never materialized separately,
    so the masked cotangent costs one read of ``y`` on top of the read of
    ``g`` the downstream dx/dw kernels do anyway. Identity / bias-only
    epilogues pass ``g`` through untouched (no launch at all).
    """
    epi = epilib.canonical(epilogue)
    if epi is None or not epi.saves_output:
        return g
    return _epilogue_grad_call(g, y, epi, tile_m=tile_m, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("epi", "tile_m", "interpret")
)
def _epilogue_grad_call(g, y, epi, *, tile_m=None, interpret=None):
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, m, mw, c = g.shape
    tm = min(tile_m or 8, m)
    n_t = pl.cdiv(m, tm)
    if m % tm:  # zero-pad rows so every tile is full (cropped below)
        pad = ((0, 0), (0, n_t * tm - m), (0, 0), (0, 0))
        g = jnp.pad(g, pad)
        y = jnp.pad(y, pad)
    spec = pl.BlockSpec((1, tm, mw, c), lambda bb, it: (bb, it, 0, 0))
    out = pl.pallas_call(
        functools.partial(_epilogue_grad_kernel, epi=epi),
        grid=(b, n_t),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
        compiler_params=compiler_params("parallel", "parallel"),
        name="tconv_epilogue_grad",
        interpret=interpret,
    )(g, y)
    return out[:, :m]


# ------------------------------------------------------------------ dx

def _dx_kernel(g_ref, w_ref, o_ref, *, R, th, tw, wsels):
    """One (batch, i_tile, j_tile, cin_tile, cout_tile) grid step: all four
    parity-plane correlations from one halo'd tile of the plane stack."""
    co = pl.program_id(4)
    ci = o_ref.shape[-1]
    acc = jnp.zeros((th * tw, ci), jnp.float32)
    for ph in range(4):
        gph = g_ref[ph, 0]          # (th + R - 1, tw + R - 1, cout_tile)
        wk = w_ref[wsels[ph]]       # (R, R, cout_tile, cin_tile), transposed
        for p in range(R):
            for q in range(R):
                # correlation with the flipped sub-kernel: tap (p, q) reads
                # the window at static origin (R-1-p, R-1-q)
                window = gph[
                    R - 1 - p : R - 1 - p + th,
                    R - 1 - q : R - 1 - q + tw, :,
                ].reshape(th * tw, -1)
                acc += jnp.dot(
                    window, wk[p, q], preferred_element_type=jnp.float32
                )

    @pl.when(co == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += acc.reshape(1, th, tw, ci)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_in", "padding", "tile_h", "tile_w", "cin_tile", "cout_tile",
        "interpret",
    ),
)
def transpose_conv2d_dx_pallas(
    g: jnp.ndarray,
    kernel: jnp.ndarray,
    n_in: int,
    padding: int = 0,
    *,
    tile_h: int | None = None,
    tile_w: int | None = None,
    cin_tile: int | None = None,
    cout_tile: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Input gradient of the unified transpose conv as one Pallas launch.

    g: (B, M, M, Cout) cotangent of the forward output; kernel: (n, n, Cin,
    Cout) HWIO primal weights. Returns dx (B, n_in, n_in, Cin), fp32 (the
    cotangent is cast to the kernel dtype so bf16 weights run bf16 MXU taps;
    accumulation is fp32 either way).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, m, _, cout = g.shape
    n_k = kernel.shape[0]
    cin = kernel.shape[2]
    if m != seg.output_size(n_in, n_k, padding):
        raise ValueError(
            f"cotangent extent {m} != output_size({n_in}, {n_k}, {padding})"
        )
    R = seg.ceil_half(n_k)

    plans, pad_lo, _ = seg.plan_phases(n_in, n_k, padding)
    # dx[i] = sum_ph sum_p g_ph[i + offr(pr) - p] . k_ph[p]  (see module doc)
    roffs = (pad_lo - plans[0].row0, pad_lo - plans[2].row0)  # by row parity
    coffs = (pad_lo - plans[0].col0, pad_lo - plans[1].col0)  # by col parity

    dth, dtw, dci, dco = default_bwd_tiles(n_in, n_k, padding, cin, cout)
    th = min(tile_h or dth, n_in)
    tw = min(tile_w or dtw, n_in)
    n_h, n_w = pl.cdiv(n_in, th), pl.cdiv(n_in, tw)
    he, we = n_h * th + R - 1, n_w * tw + R - 1  # shifted plane extents

    # Pre-shift each parity plane so the kernel reads every phase at the SAME
    # tile-local origin i + (R-1) - p: plane (pr, pc) is placed at offset
    # lo = (R-1) - offr(pr) (zero-fill; over-computed rows i >= n_in read
    # zeros and are cropped after the launch).
    planes = _parity_planes(g)  # (4, B, Hp, Wp, Cout)
    shifted = []
    for pr in range(2):
        for pc in range(2):
            p_ = planes[2 * pr + pc]
            p_ = _place(p_, 1, (R - 1) - roffs[pr], he)
            p_ = _place(p_, 2, (R - 1) - coffs[pc], we)
            shifted.append(p_)
    gs = jnp.stack(shifted).astype(kernel.dtype)  # bf16-in when weights are

    # transposed sub-kernel stack: contraction is over Cout
    wt = seg.stack_subkernels(kernel).transpose(0, 1, 2, 4, 3)
    ci_t = cin_tile or dci
    co_t = cout_tile or dco
    if cin % ci_t or cout % co_t:
        raise ValueError(f"cin={cin} % {ci_t} or cout={cout} % {co_t} != 0")

    n_co = cout // co_t
    grid = (b, n_h, n_w, cin // ci_t, n_co)
    out = pl.pallas_call(
        functools.partial(
            _dx_kernel, R=R, th=th, tw=tw, wsels=_wsels(padding)
        ),
        grid=grid,
        in_specs=[
            # halo'd tile of all four (pre-shifted) parity planes: overlapping
            # windows -> Element block dims (element offsets)
            pl.BlockSpec(
                halo_block(4, 1, th + R - 1, tw + R - 1, co_t),
                lambda bb, ih, iw, cc, oc: (
                    0, bb, ih * th, tile_offset(iw, tw, n_w),
                    tile_offset(oc, co_t, n_co),
                ),
            ),
            pl.BlockSpec(
                (4, R, R, co_t, ci_t),
                lambda bb, ih, iw, cc, oc: (0, 0, 0, oc, cc),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, th, tw, ci_t),
            lambda bb, ih, iw, cc, oc: (bb, ih, iw, cc),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_h * th, n_w * tw, cin), jnp.float32
        ),
        compiler_params=compiler_params(
            "parallel", "parallel", "parallel", "parallel", "arbitrary"
        ),
        name="tconv_dx",
        interpret=interpret,
    )(gs, wt)
    return out[:, :n_in, :n_in, :]


# ------------------------------------------------------------------ dw

def _dw_kernel(x_ref, g_ref, *out_refs, R, th, wp, roffs, coffs, wsels,
               with_db):
    """One (cout_tile, cin_tile, batch, h_tile) grid step: every (phase,
    p, q) tap contracts the tile's spatial axis into the stacked sub-kernel
    gradient, accumulated across the trailing (cin_tile, batch, h_tile)
    grid axes.

    ``with_db``: a second ``(1, cout_tile)`` output accumulates
    ``db = sum_{b,space} g`` in the SAME pass — the parity-plane tiles are
    already in VMEM for the dw taps, so the bias gradient costs zero extra
    HBM reads. The db block is revisited by every (cin, batch, h) step but
    only accumulated on the first cin tile (g doesn't depend on cin).
    """
    o_ref = out_refs[0]
    ci = pl.program_id(1)
    bi = pl.program_id(2)
    ih = pl.program_id(3)
    x = x_ref[0]  # (th + dr + R - 1, wp + dc + R - 1, cin_tile)

    # the dw block is per (cout_tile, cin_tile): first visit is (bi, ih) == 0
    @pl.when((bi == 0) & (ih == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if with_db:
        db_ref = out_refs[1]

        @pl.when((ci == 0) & (bi == 0) & (ih == 0))
        def _init_db():
            db_ref[...] = jnp.zeros_like(db_ref)

        @pl.when(ci == 0)  # g is cin-independent: reduce it once
        def _acc_db():
            gall = g_ref[:, 0]  # (4, th, wp, cout_tile)
            db_ref[...] += gall.astype(jnp.float32).sum((0, 1, 2))[None]

    for ph in range(4):
        pr, pc = ph // 2, ph % 2
        g2 = g_ref[ph, 0].reshape(th * wp, -1)  # (th * wp, cout_tile)
        r0, c0 = roffs[pr], coffs[pc]           # static tile-local origin
        kidx = wsels[ph]
        for p in range(R):
            for q in range(R):
                window = x[
                    r0 + p : r0 + p + th, c0 + q : c0 + q + wp, :
                ].reshape(th * wp, -1)
                # (cin_tile, cout_tile) <- contract the spatial axis
                o_ref[kidx, p, q] += lax.dot_general(
                    window, g2, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_k", "padding", "tile_h", "cin_tile", "cout_tile", "interpret",
        "with_db",
    ),
)
def transpose_conv2d_dw_pallas(
    x: jnp.ndarray,
    g: jnp.ndarray,
    n_k: int,
    padding: int = 0,
    *,
    tile_h: int | None = None,
    cin_tile: int | None = None,
    cout_tile: int | None = None,
    interpret: bool | None = None,
    with_db: bool = False,
):
    """Weight gradient of the unified transpose conv as one Pallas launch.

    x: (B, N, N, Cin) primal input; g: (B, M, M, Cout) cotangent. Returns
    dw (n_k, n_k, Cin, Cout), fp32, assembled from the per-parity stacked
    gradient (zero-padded stack taps are sliced away before the merge).

    ``with_db=True`` additionally reduces the bias gradient
    ``db = sum_{b,space} g`` (Cout,) in the same launch via a second
    grid-carried accumulator — the epilogue'd VJP's dw/db pass — and
    returns ``(dw, db)``.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, n_in, _, cin = x.shape
    m = g.shape[1]
    cout = g.shape[-1]
    if m != seg.output_size(n_in, n_k, padding):
        raise ValueError(
            f"cotangent extent {m} != output_size({n_in}, {n_k}, {padding})"
        )
    R = seg.ceil_half(n_k)
    hp = wp = (m + 1) // 2

    row0s, col0s, pad_lo = _phase_offsets(n_in, n_k, padding)
    base_r, base_c = min(row0s), min(col0s)
    dr, dc = max(row0s) - base_r, max(col0s) - base_c  # cross-phase skew

    th = min(tile_h or default_dw_tile(n_in, n_k, padding), hp)
    n_h = pl.cdiv(hp, th)
    hp_t = n_h * th  # rounded-up tiled plane extent

    # pad the input exactly like the forward: every tile's halo'd window
    # must be in-bounds (over-computed rows pair with zero cotangent rows)
    need_r = max(row0s) + hp_t + R - 1
    need_c = max(col0s) + wp + R - 1
    pad_hi_r = max(0, need_r - (n_in + pad_lo))
    pad_hi_c = max(0, need_c - (n_in + pad_lo))
    xp = jnp.pad(x, ((0, 0), (pad_lo, pad_hi_r), (pad_lo, pad_hi_c), (0, 0)))

    # parity planes zero-padded to the uniform tiled (hp_t, wp) extent
    gz = _parity_planes(g)
    gz = jnp.pad(gz, ((0, 0), (0, 0), (0, hp_t - gz.shape[2]), (0, 0), (0, 0)))
    gz = gz.astype(x.dtype)  # bf16-in when the primal input is

    ci_t = cin_tile or min(cin, 512)
    co_t = cout_tile or min(cout, 128)
    if cin % ci_t or cout % co_t:
        raise ValueError(f"cin={cin} % {ci_t} or cout={cout} % {co_t} != 0")

    # grid (cout_tile, cin_tile, batch, h_tile): only the leading cout axis
    # is parallel — the db accumulator block is revisited across the cin
    # axis (it accumulates only on the first cin tile), so cin joins
    # (batch, h_tile) as a sequential axis
    n_ci = cin // ci_t
    grid = (cout // co_t, n_ci, b, n_h)
    out_specs = [
        # grid-carried accumulator: one block per (cout, cin) tile,
        # revisited by every (batch, h_tile) step
        pl.BlockSpec(
            (4, R, R, ci_t, co_t),
            lambda oc, cc, bb, ih: (0, 0, 0, cc, oc),
        ),
    ]
    out_shape = [jax.ShapeDtypeStruct((4, R, R, cin, cout), jnp.float32)]
    if with_db:
        # db accumulator: ONE (1, co_t) block per cout tile, revisited by
        # every (cin, batch, h_tile) step
        out_specs.append(
            pl.BlockSpec((1, co_t), lambda oc, cc, bb, ih: (0, oc))
        )
        out_shape.append(jax.ShapeDtypeStruct((1, cout), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(
            _dw_kernel, R=R, th=th, wp=wp,
            roffs=tuple(r - base_r for r in row0s),
            coffs=tuple(c - base_c for c in col0s),
            wsels=_wsels(padding), with_db=with_db,
        ),
        grid=grid,
        in_specs=[
            # the forward's halo'd input tile (Element offsets)
            pl.BlockSpec(
                halo_block(1, th + dr + R - 1, wp + dc + R - 1, ci_t),
                lambda oc, cc, bb, ih: (
                    bb, base_r + ih * th, base_c, tile_offset(cc, ci_t, n_ci)
                ),
            ),
            pl.BlockSpec(
                (4, 1, th, wp, co_t),
                lambda oc, cc, bb, ih: (0, bb, ih, 0, oc),
            ),
        ],
        out_specs=out_specs if with_db else out_specs[0],
        out_shape=tuple(out_shape) if with_db else out_shape[0],
        # only the db accumulator is revisited across the cin axis; without
        # it the cin tiles stay parallel exactly as before
        compiler_params=compiler_params(
            "parallel", "arbitrary" if with_db else "parallel",
            "arbitrary", "arbitrary",
        ),
        name="tconv_dw",
        interpret=interpret,
    )(xp, gz)
    stack = outs[0] if with_db else outs

    # stacked (4, R, R, Cin, Cout) -> (n, n, Cin, Cout): slice each
    # sub-kernel gradient to its true extent (dropping the zero-pad taps'
    # garbage) and re-interleave
    subs = []
    for r in range(2):
        for s in range(2):
            rr, cc = seg.subkernel_shape(n_k, r, s)
            subs.append(stack[2 * r + s, :rr, :cc])
    dw = seg.merge_subkernels(seg.SubKernels(*subs), n_k)
    if with_db:
        return dw, outs[1][0]
    return dw


def transpose_conv2d_bwd_pallas(
    x: jnp.ndarray,
    kernel: jnp.ndarray,
    g: jnp.ndarray,
    padding: int = 0,
    *,
    tile_h: int | None = None,
    tile_w: int | None = None,
    dw_tile_h: int | None = None,
    interpret: bool | None = None,
    epilogue=None,
    y: jnp.ndarray | None = None,
):
    """Full segregated Pallas backward: (dx, dw[, db]) for one forward call.

    ``tile_h``/``tile_w`` pin the dx kernel's spatial tiling (e.g. the
    autotuner's measured winner); ``dw_tile_h`` pins the dw reduction tile.
    Gradients come back in fp32 (callers cast to the primal dtypes).

    ``epilogue`` is the layer's fused :class:`~repro.kernels.epilogue
    .Epilogue`: the cotangent is first masked by the fused Pallas prologue
    ``gm = g · act'(y)`` (``y`` = the saved forward output, required iff the
    epilogue has an activation), then the dx/dw kernels consume the
    PRE-masked ``gm``. With ``epilogue.bias`` the dw pass also reduces
    ``db`` (same launch) and the return grows to ``(dx, dw, db)``.
    """
    epi = epilib.canonical(epilogue)
    if epi is not None and epi.saves_output:
        if y is None:
            raise ValueError(
                f"epilogue {epi.tag()!r} backward needs the saved output y"
            )
        g = epilogue_grad_pallas(g, y, epi, interpret=interpret)
    dx = transpose_conv2d_dx_pallas(
        g, kernel, x.shape[1], padding,
        tile_h=tile_h, tile_w=tile_w, interpret=interpret,
    )
    with_db = epi is not None and epi.bias
    dw = transpose_conv2d_dw_pallas(
        x, g, kernel.shape[0], padding, tile_h=dw_tile_h, interpret=interpret,
        with_db=with_db,
    )
    if with_db:
        dw, db = dw
        return dx, dw, db
    return dx, dw
