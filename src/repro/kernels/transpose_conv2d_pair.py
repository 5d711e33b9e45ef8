"""Layer-pair megafused transpose convolution: VMEM-resident interface.

Executes TWO stacked stride-2 transpose-conv layers (producer -> consumer)
from a single Pallas launch. The producer's interleaved output slab — the
*interface* activation between the layers — is accumulated into a VMEM
scratch buffer, the interface epilogue (``+ bias``, activation) applies on
that fp32 accumulator, and the consumer's four sub-kernel phases consume the
slab directly. The interface activation therefore **never touches HBM**:
the only HBM traffic is the pair's true input, both sub-kernel stacks, the
biases, and the final output — the logical endpoint of the paper's
touch-each-output-once argument, extended across a layer boundary
(cf. HUGE^2, arXiv:1907.11210, which wins on decomposed GAN deconv stacks
precisely by eliminating inter-stage memory traffic).

Grid layout
-----------

``(batch, cout2_tile, mid_tile, cin_tile)`` with ``dimension_semantics =
(parallel, parallel, arbitrary, arbitrary)``. The two inner axes carry loop
dependencies:

* ``cin`` (innermost) accumulates the producer's reduction into the
  interface scratch slab (``@pl.when(ci == 0)`` zero-init);
* at the LAST ``cin`` step the interface epilogue applies and the consumer
  runs its four phase accumulations for the current ``mid`` (= interface
  channel) block, accumulating into the output block — which the ``mid``
  axis revisits (``@pl.when(mid == 0)`` init), so the consumer's reduction
  over interface channels happens entirely in VMEM too.

The consumer's spatial extent is NOT tiled: legality (enforced by the plan
pass via :func:`pair_vmem_bytes`) requires the producer's whole output plane
plus the consumer's halo to fit the VMEM budget — exactly the channel-deep,
small-spatial generator heads this fusion targets. Both layers' sub-kernel
stacks ride in VMEM; output-parity -> sub-kernel selection (including the
odd-padding swap, paper §3.4) is static per layer.

Numerics match two back-to-back :func:`transpose_conv2d_pallas` launches
tap for tap: same fp32 accumulation, same interface crop/re-pad semantics
(over-computed interleave rows are cropped before the consumer's zero halo
is applied), same epilogue placement on the fp32 accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import segregation as seg
from repro.kernels import epilogue as epilib
from repro.kernels.mosaic import block_bytes, compiler_params, pipelined_bytes
from repro.kernels.transpose_conv2d import _phase_offsets, interleave_planes


def _snap(c: int, t: int) -> int:
    """Largest default tile <= t that divides c (falls back to c itself)."""
    t = min(c, t)
    return t if c % t == 0 else c


def default_pair_tiles(cin: int, mid: int, cout: int):
    """Default (cin_tile, mid_tile, cout_tile) of the pair kernel.

    Single source of the pair tile defaults — the plan pass's VMEM budget
    estimator and the autotuner's pair roofline model both import this so
    their geometry can never drift from what the kernel actually runs.
    """
    return _snap(cin, 256), _snap(mid, 128), _snap(cout, 512)


def pair_geometry(n_in: int, n_k: int, padding: int) -> dict:
    """Static geometry shared by the kernel, the VMEM estimator and the
    autotuner's pair roofline model.

    ``m1`` is the interface extent, ``m2`` the pair output extent; ``np1``
    the padded-input plane extent the producer reads; ``s2`` the padded
    interface extent the consumer's phase windows cover (low ``pad_lo2``
    zeros + the ``m1`` interface + high zeros for over-computed windows).
    """
    R = seg.ceil_half(n_k)
    m1 = seg.output_size(n_in, n_k, padding)
    m2 = seg.output_size(m1, n_k, padding)
    hp1, hp2 = (m1 + 1) // 2, (m2 + 1) // 2
    row0s1, col0s1, pad_lo1 = _phase_offsets(n_in, n_k, padding)
    row0s2, col0s2, pad_lo2 = _phase_offsets(m1, n_k, padding)
    need1 = max(row0s1 + col0s1) + hp1 + R - 1
    pad_hi1 = max(0, need1 - (n_in + pad_lo1))
    need2 = max(row0s2 + col0s2) + hp2 + R - 1
    pad_hi2 = max(0, need2 - (m1 + pad_lo2))
    return dict(
        R=R, m1=m1, m2=m2, hp1=hp1, hp2=hp2,
        row0s1=row0s1, col0s1=col0s1, pad_lo1=pad_lo1, pad_hi1=pad_hi1,
        np1=pad_lo1 + n_in + pad_hi1,
        row0s2=row0s2, col0s2=col0s2, pad_lo2=pad_lo2, pad_hi2=pad_hi2,
        s2=pad_lo2 + m1 + pad_hi2,
    )


def pair_vmem_bytes(
    n_in: int,
    n_k: int,
    cin: int,
    mid: int,
    cout: int,
    padding: int,
    dtype_bytes: int = 4,
    tiles: tuple[int, int, int] | None = None,
) -> int:
    """Deterministic per-grid-step VMEM residency estimate of the pair kernel.

    Sums the operand blocks exactly as the BlockSpecs below shape them, in
    Mosaic's padded tile layout (:mod:`repro.kernels.mosaic`): padded input
    plane tile, both stacked sub-kernel blocks, the bias blocks and the fp32
    output planes, all double-buffered, plus the fp32 interface scratch
    slab. The plan pass fuses a pair iff this passes
    :func:`repro.kernels.mosaic.fits_vmem`.
    """
    g = pair_geometry(n_in, n_k, padding)
    tci, tmid, tco = tiles or default_pair_tiles(cin, mid, cout)
    R, np1, hp1, hp2 = g["R"], g["np1"], g["hp1"], g["hp2"]
    return pipelined_bytes(
        ((np1, np1, tci), dtype_bytes),                  # input plane tile
        ((4, R, R, tci, tmid), dtype_bytes),             # producer stack
        ((4, R, R, tmid, tco), 4),                       # consumer stack
        ((1, tmid), 4), ((1, tco), 4),                   # bias blocks
        ((4, hp2, hp2, tco), 4),                         # output planes
    ) + block_bytes((2 * hp1, 2 * hp1, tmid), 4)         # interface scratch


def _pair_kernel(
    x_ref, w1_ref, w2_ref, *rest,
    R, hp1, m1, roffs1, coffs1, wsels1,
    hp2, pad_lo2, pad_hi2, roffs2, coffs2, wsels2,
    epi1, epi2,
):
    """One (batch, cout2_tile, mid_tile, cin_tile) grid step.

    ``rest`` is ``([b1_ref,] [b2_ref,] o_ref, scratch_ref)`` — the bias refs
    are present iff the corresponding epilogue carries a bias; the VMEM
    scratch ref (the interface slab) always comes last, after the output.
    """
    n_bias = sum(
        1 for e in (epi1, epi2) if e is not None and e.bias
    )
    b1_ref = rest[0] if epi1 is not None and epi1.bias else None
    b2_ref = rest[n_bias - 1] if epi2 is not None and epi2.bias else None
    o_ref, s_ref = rest[-2], rest[-1]
    mid = pl.program_id(2)
    ci = pl.program_id(3)

    x = x_ref[0]  # (np1, np1, tci) padded input plane tile
    tm = s_ref.shape[-1]

    # ---- producer: all four phases into the interleaved interface slab
    planes = []
    for pr in range(2):
        for pc in range(2):
            r0, c0 = roffs1[pr], coffs1[pc]
            wk = w1_ref[wsels1[2 * pr + pc]]  # (R, R, tci, tm)
            acc = jnp.zeros((hp1 * hp1, tm), jnp.float32)
            for p in range(R):
                for q in range(R):
                    window = x[
                        r0 + p : r0 + p + hp1, c0 + q : c0 + q + hp1, :
                    ].reshape(hp1 * hp1, -1)
                    acc += jnp.dot(
                        window, wk[p, q], preferred_element_type=jnp.float32
                    )
            planes.append(acc.reshape(hp1, hp1, tm))
    block = jnp.stack(planes).reshape(2, 2, hp1, hp1, tm)
    block = block.transpose(2, 0, 3, 1, 4).reshape(2 * hp1, 2 * hp1, tm)

    @pl.when(ci == 0)
    def _init_scratch():
        s_ref[...] = jnp.zeros_like(s_ref)

    s_ref[...] += block

    # ---- at the last cin step: interface epilogue on the fp32 slab, then
    # the consumer's four phases consume it — all without leaving VMEM
    @pl.when(ci == pl.num_programs(3) - 1)
    def _consume():
        y1 = s_ref[...]
        if b1_ref is not None:
            y1 = y1 + b1_ref[0]
        if epi1 is not None:
            y1 = epi1.apply_act(y1)
        # crop the over-computed interleave rows/cols, re-apply the
        # consumer's zero halo (same semantics as the HBM round trip)
        y1 = y1[:m1, :m1, :]
        xi = jnp.pad(
            y1, ((pad_lo2, pad_hi2), (pad_lo2, pad_hi2), (0, 0))
        )
        ct = o_ref.shape[-1]
        planes2 = []
        for pr in range(2):
            for pc in range(2):
                r0, c0 = roffs2[pr], coffs2[pc]
                wk = w2_ref[wsels2[2 * pr + pc]]  # (R, R, tm, ct)
                acc = jnp.zeros((hp2 * hp2, ct), jnp.float32)
                for p in range(R):
                    for q in range(R):
                        window = xi[
                            r0 + p : r0 + p + hp2, c0 + q : c0 + q + hp2, :
                        ].reshape(hp2 * hp2, -1)
                        acc += jnp.dot(
                            window, wk[p, q],
                            preferred_element_type=jnp.float32,
                        )
                planes2.append(acc.reshape(hp2, hp2, ct))

        @pl.when(mid == 0)
        def _init_out():
            o_ref[...] = jnp.zeros_like(o_ref)

        for ph, plane in enumerate(planes2):  # block (1, 2, 2, hp2, hp2, ct)
            o_ref[0, ph // 2, ph % 2] += plane

        if epi2 is not None:
            @pl.when(mid == pl.num_programs(2) - 1)
            def _epilogue():
                y = o_ref[...]
                if b2_ref is not None:
                    y = y + b2_ref[0]
                o_ref[...] = epi2.apply_act(y)


@functools.partial(
    jax.jit,
    static_argnames=(
        "padding", "cin_tile", "mid_tile", "cout_tile", "interpret",
        "epilogue1", "epilogue2",
    ),
)
def transpose_conv2d_pair_pallas(
    x: jnp.ndarray,
    k1: jnp.ndarray,
    k2: jnp.ndarray,
    padding: int = 0,
    *,
    cin_tile: int | None = None,
    mid_tile: int | None = None,
    cout_tile: int | None = None,
    interpret: bool | None = None,
    epilogue1=None,
    bias1: jnp.ndarray | None = None,
    epilogue2=None,
    bias2: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Two stacked transpose-conv layers from one launch, interface in VMEM.

    x: (B, N, N, C0) NHWC; k1: (n, n, C0, C1); k2: (n, n, C1, C2), both
    HWIO with the same ``padding``. Returns (B, M2, M2, C2) fp32 where
    ``M1 = 2N - n + 2*padding`` and ``M2 = 2*M1 - n + 2*padding``.
    ``epilogue1``/``bias1`` is the *interface* epilogue (applied on the fp32
    scratch accumulator between the layers); ``epilogue2``/``bias2`` the
    output epilogue. Inputs may be bf16; accumulation is always fp32.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    epi1 = epilib.canonical(epilogue1)
    epi2 = epilib.canonical(epilogue2)
    for name, epi, bias in (("1", epi1, bias1), ("2", epi2, bias2)):
        if (epi is not None and epi.bias) != (bias is not None):
            raise ValueError(
                f"epilogue{name} {epi.tag() if epi else None!r} and "
                f"bias{name}={'set' if bias is not None else None} disagree"
            )
    b, n_in, _, c0 = x.shape
    n_k = k1.shape[0]
    if k2.shape[0] != n_k:
        raise ValueError(f"kernel extents differ: {k1.shape} vs {k2.shape}")
    c1, c2 = k1.shape[3], k2.shape[3]
    if k1.shape[2] != c0 or k2.shape[2] != c1:
        raise ValueError(
            f"channel chain broken: x{x.shape} k1{k1.shape} k2{k2.shape}"
        )
    g = pair_geometry(n_in, n_k, padding)
    R, hp1, hp2, m1, m2 = g["R"], g["hp1"], g["hp2"], g["m1"], g["m2"]

    dci, dmid, dco = default_pair_tiles(c0, c1, c2)
    tci = cin_tile or dci
    tmid = mid_tile or dmid
    tco = cout_tile or dco
    if c0 % tci or c1 % tmid or c2 % tco:
        raise ValueError(
            f"cin={c0} % {tci} or mid={c1} % {tmid} or cout={c2} % {tco} != 0"
        )

    xp = jnp.pad(
        x,
        ((0, 0), (g["pad_lo1"], g["pad_hi1"]), (g["pad_lo1"], g["pad_hi1"]),
         (0, 0)),
    )
    w1 = seg.stack_subkernels(k1)  # (4, R, R, C0, C1)
    w2 = seg.stack_subkernels(k2)  # (4, R, R, C1, C2)
    wsels = tuple(
        2 * ((pr + padding) % 2) + ((pc + padding) % 2)
        for pr in range(2) for pc in range(2)
    )

    grid = (b, c2 // tco, c1 // tmid, c0 // tci)
    np1 = g["np1"]
    in_specs = [
        # the producer's full padded input plane (legality bounds N): one
        # channel-tile slab per grid step
        pl.BlockSpec((1, np1, np1, tci), lambda bb, co, md, cc: (bb, 0, 0, cc)),
        pl.BlockSpec(
            (4, R, R, tci, tmid), lambda bb, co, md, cc: (0, 0, 0, cc, md)
        ),
        pl.BlockSpec(
            (4, R, R, tmid, tco), lambda bb, co, md, cc: (0, 0, 0, md, co)
        ),
    ]
    operands = [xp, w1, w2]
    if epi1 is not None and epi1.bias:
        in_specs.append(pl.BlockSpec((1, tmid), lambda bb, co, md, cc: (0, md)))
        operands.append(bias1.reshape(1, c1).astype(jnp.float32))
    if epi2 is not None and epi2.bias:
        in_specs.append(pl.BlockSpec((1, tco), lambda bb, co, md, cc: (0, co)))
        operands.append(bias2.reshape(1, c2).astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(
            _pair_kernel,
            R=R, hp1=hp1, m1=m1,
            roffs1=g["row0s1"], coffs1=g["col0s1"], wsels1=wsels,
            hp2=hp2, pad_lo2=g["pad_lo2"], pad_hi2=g["pad_hi2"],
            roffs2=g["row0s2"], coffs2=g["col0s2"], wsels2=wsels,
            epi1=epi1, epi2=epi2,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 2, 2, hp2, hp2, tco),
            lambda bb, co, md, cc: (bb, 0, 0, 0, 0, co),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 2, 2, hp2, hp2, c2), jnp.float32),
        # the interface slab: a VMEM scratch accumulator, never an HBM
        # operand — this is the buffer the spy test pins
        scratch_shapes=[pltpu.VMEM((2 * hp1, 2 * hp1, tmid), jnp.float32)],
        compiler_params=compiler_params(
            "parallel", "parallel", "arbitrary", "arbitrary",
        ),
        interpret=interpret,
    )(*operands)
    return interleave_planes(out)[:, :m2, :m2, :]
