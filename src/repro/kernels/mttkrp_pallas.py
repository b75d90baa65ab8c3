"""Pallas TPU kernel: blocked one-hot segment-matmul MTTKRP.

This is the TPU-native re-design of SPLATT's parallel MTTKRP (the paper's
critical kernel).  The CPU algorithm walks a CSF pointer tree with per-row
mutexes; on a TPU we instead exploit the MXU:

  * non-zeros arrive pre-sorted and *tile-aligned* (the unified ``CSF``
    workspace): every
    block of ``BLOCK`` non-zeros writes exactly one ``ROW_TILE x R`` output
    tile, and the block -> tile map is non-decreasing, so the output tile
    stays resident in VMEM across consecutive grid steps (sequential TPU
    grid) and is flushed exactly once;
  * output-row collisions *inside* a block are resolved by a one-hot
    "segment matrix" ``S[m, b] = (row[b] == tile_start + m)`` matmul:
    ``out_tile += S @ (vals * Brows * Crows)`` — the MXU's sum reduction
    performs, in hardware, what SPLATT's mutex pool / atomics serialize.
    This is the paper's sync-vs-atomic finding taken to its TPU conclusion:
    conflict resolution as dense compute instead of synchronization;
  * the elementwise Khatri-Rao product (vals x Brows x Crows) is fused into
    the kernel so the (nnz x R) partial-product tensor never round-trips
    HBM — only the gathered factor rows stream in.

VMEM budget per grid step (defaults BLOCK=512, ROW_TILE=128, R padded 128):
  brows + crows: 2 x 512 x 128 x 4B = 512 KiB
  one-hot + prod + out tile:   (128x512 + 512x128 + 128x128) x 4B = 576 KiB
comfortably inside a v5e core's ~16 MiB VMEM with double buffering.

The MXU work per step is a (128 x 512) @ (512 x 128) matmul — both dims
hardware-aligned (multiples of 128 / 8 sublanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANE = 128  # TPU lane width: rank is padded to a multiple of this


def _kernel(tile_map_ref, rows_ref, vals_ref, brows_ref, crows_ref, out_ref,
            *, row_tile: int, block: int):
    b = pl.program_id(0)
    tile = tile_map_ref[b]
    prev_tile = tile_map_ref[jnp.maximum(b - 1, 0)]
    is_first_visit = jnp.logical_or(b == 0, tile != prev_tile)

    @pl.when(is_first_visit)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # fused Khatri-Rao partial product: (BLOCK, R)
    prod = (
        vals_ref[0, 0][:, None].astype(jnp.float32)
        * brows_ref[0].astype(jnp.float32)
        * crows_ref[0].astype(jnp.float32)
    )
    # one-hot segment matrix: S[m, n] = (rows[n] == tile*row_tile + m)
    local = rows_ref[0, 0] - tile * row_tile  # (BLOCK,), in [0, row_tile)
    sel = (
        jax.lax.broadcasted_iota(jnp.int32, (row_tile, block), 0)
        == local[None, :]
    )
    # MXU: collisions inside the block are summed by the matmul itself.
    # HIGHEST: the one-hot operand is exact in bfloat16 but the products
    # are not, and the MTTKRP is float32 end to end
    out_ref[...] += jax.lax.dot(
        sel.astype(jnp.float32), prod, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32
    )


def mttkrp_pallas_call(
    rows: Array,        # (nblocks, 1, BLOCK) int32, tile-aligned sorted rows
    vals: Array,        # (nblocks, 1, BLOCK)
    brows: Array,       # (nblocks, BLOCK, RP) gathered factor rows
    crows: Array,       # (nblocks, BLOCK, RP) gathered (and pre-multiplied
                        #  for order > 3) remaining factor rows
    block_tile: Array,  # (nblocks,) int32 non-decreasing block -> tile map
    *,
    num_row_tiles: int,
    row_tile: int,
    interpret: bool,
) -> Array:
    nblocks, _, block = rows.shape
    rp = brows.shape[-1]
    if rp % LANE:
        raise ValueError(f"rank must be padded to {LANE}, got {rp}")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[
            # (1, 1, block): the last two block dims equal the array's
            # (1, block), which the TPU lowering requires of a 1-row block
            pl.BlockSpec((1, 1, block), lambda b, tm: (b, 0, 0)),
            pl.BlockSpec((1, 1, block), lambda b, tm: (b, 0, 0)),
            pl.BlockSpec((1, block, rp), lambda b, tm: (b, 0, 0)),
            pl.BlockSpec((1, block, rp), lambda b, tm: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, rp), lambda b, tm: (tm[b], 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, row_tile=row_tile, block=block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_row_tiles * row_tile, rp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential: accumulation
        ),
        interpret=interpret,
    )(block_tile, rows, vals, brows, crows)
    return out
