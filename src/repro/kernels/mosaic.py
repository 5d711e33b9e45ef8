"""What the TPU kernel compiler (Mosaic) asks of every Pallas kernel here.

* :func:`compiler_params` — the kernels' ``pltpu.CompilerParams``: grid
  dimension semantics plus one scoped-VMEM limit for all of them.
* :func:`halo_block` / :func:`tile_offset` — overlapping (halo'd) input
  windows. Mosaic refuses to mix Element and Blocked block dims, and it must
  prove every element offset along a tiled dim (the last two) a multiple of
  that dim's tiling.
* :func:`block_bytes` / :func:`fits_vmem` — the VMEM footprint model the
  whole-plane kernels (phase, implicit-GEMM, layer pair) are screened with.
  Their VMEM grows with the spatial plane, so each one is a dispatch or
  autotune candidate only at layer shapes whose estimate fits
  :data:`VMEM_BUDGET_BYTES`; the spatially tiled kernels are bounded by
  their tiles and need no screen.
"""
from __future__ import annotations

import math

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM each kernel may use. The compiler's default scope is 16 MiB
# on v5e, whose VMEM is 128 MiB.
VMEM_LIMIT_BYTES = 32 * 2**20
# What a screened kernel's estimate may claim; the rest of the limit is left
# for Mosaic's own temporaries, which the model does not count.
VMEM_BUDGET_BYTES = 24 * 2**20


def compiler_params(*semantics: str) -> pltpu.CompilerParams:
    """``dimension_semantics`` for the grid plus the shared VMEM limit."""
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )


def halo_block(*sizes: int) -> tuple:
    """Block shape of an overlapping window: every dim an Element dim (the
    index map then returns element offsets, not block indices)."""
    return tuple(pl.Element(s) for s in sizes)


def tile_offset(idx, size: int, n_tiles: int):
    """Element offset of tile ``idx`` along a tiled (sublane or lane) dim.

    A single tile gets a literal 0, which Mosaic can prove aligned; several
    tiles need a tile ``size`` that is a multiple of the dim's tiling.
    """
    return 0 if n_tiles == 1 else idx * size


def block_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of one block: the minor dim padded to 128 lanes, the
    second-minor to the dtype's sublane tile (8 rows of 32 bits)."""
    *lead, rows, lanes = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = 8 * max(1, 4 // itemsize)
    return (math.prod(lead) * (-(-rows // sub) * sub)
            * (-(-lanes // 128) * 128) * itemsize)


def pipelined_bytes(*blocks) -> int:
    """Bytes of the grid-pipelined operands, each ``(shape, itemsize)``:
    Pallas double-buffers every input and output block."""
    return 2 * sum(block_bytes(s, i) for s, i in blocks)


def fits_vmem(nbytes: int) -> bool:
    return nbytes <= VMEM_BUDGET_BYTES
