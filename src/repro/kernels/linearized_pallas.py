"""Pallas TPU kernel: MTTKRP over the linearized workspace, in-kernel decode.

Same blocked one-hot segment-matmul as ``mttkrp_pallas.py`` — the stream is
sorted and tile-aligned by the sort mode's output row, so the output tile
stays VMEM-resident across consecutive grid steps and collisions inside a
block are resolved by the MXU matmul.  The one structural difference is the
row operand: instead of a pre-extracted ``rows`` array the kernel receives
the packed index's hi/lo uint32 words and recovers the output row *inside
the kernel* with the static shift/mask decode (``decode_field``) — the
ALTO move.  The decode is two or three integer vector ops per block on the
VPU, fully overlapped with the MXU matmul of the previous block, so the
mode-agnostic format costs essentially nothing on its sort mode.

(For non-sort modes the stream is not ordered by the output row and the
block -> tile map does not exist; those fall back to the jnp scatter impl —
see ``kernels/ops.mttkrp_lin``.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.linearized import decode_field

from .mttkrp_pallas import LANE, segment_sum

Array = jax.Array


def _kernel(tile_map_ref, hi_ref, lo_ref, vals_ref, brows_ref, crows_ref,
            out_ref, *, row_tile: int, offset: int, width: int):
    b = pl.program_id(0)
    tile = tile_map_ref[b]
    prev_tile = tile_map_ref[jnp.maximum(b - 1, 0)]
    is_first_visit = jnp.logical_or(b == 0, tile != prev_tile)

    @pl.when(is_first_visit)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # in-kernel coordinate decode: static shift + mask on the packed words
    rows = decode_field(hi_ref[0, 0], lo_ref[0, 0], offset, width)  # (BLOCK,)

    # fused Khatri-Rao partial product: (BLOCK, R)
    prod = (
        vals_ref[0, 0][:, None].astype(jnp.float32)
        * brows_ref[0].astype(jnp.float32)
        * crows_ref[0].astype(jnp.float32)
    )
    # collisions inside the block are summed by the MXU
    local = rows - tile * row_tile  # (BLOCK,), in [0, row_tile)
    out_ref[...] += segment_sum(local, prod, row_tile)


def mttkrp_lin_pallas_call(
    hi: Array,          # (nblocks, 1, BLOCK) uint32 high words, sorted stream
    lo: Array,          # (nblocks, 1, BLOCK) uint32 low words
    vals: Array,        # (nblocks, 1, BLOCK)
    brows: Array,       # (nblocks, BLOCK, RP) gathered factor rows
    crows: Array,       # (nblocks, BLOCK, RP) gathered (pre-multiplied for
                        #  order > 3) remaining factor rows
    block_tile: Array,  # (nblocks,) int32 non-decreasing block -> tile map
    *,
    num_row_tiles: int,
    row_tile: int,
    offset: int,        # sort mode's bit field position in the packed index
    width: int,
    interpret: bool,
) -> Array:
    nblocks, _, block = hi.shape
    rp = brows.shape[-1]
    if rp % LANE:
        raise ValueError(f"rank must be padded to {LANE}, got {rp}")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[
            # (1, 1, block) row blocks: see mttkrp_pallas_call
            pl.BlockSpec((1, 1, block), lambda b, tm: (b, 0, 0)),
            pl.BlockSpec((1, 1, block), lambda b, tm: (b, 0, 0)),
            pl.BlockSpec((1, 1, block), lambda b, tm: (b, 0, 0)),
            pl.BlockSpec((1, block, rp), lambda b, tm: (b, 0, 0)),
            pl.BlockSpec((1, block, rp), lambda b, tm: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, rp), lambda b, tm: (tm[b], 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, row_tile=row_tile, offset=offset,
                          width=width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_row_tiles * row_tile, rp),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential: accumulation
        ),
        interpret=interpret,
    )(block_tile, hi, lo, vals, brows, crows)
    return out
