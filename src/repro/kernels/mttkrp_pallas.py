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
  * the elementwise Khatri-Rao product (vals x Brows x Crows [x Drows ...])
    is formed in the kernel from every gathered operand, so neither the
    (nnz x R) partial product nor a product of two operands is written to
    HBM.  The gathered factor rows it is formed from are: XLA gathers them,
    lane-padded, into one (nnz x 128) array per other mode that the kernel
    streams in;
  * an operand may be gathered from a table that packs ``k`` factor rows to
    a lane row (``ops._packed``): with it comes a stream of each row's slot,
    and the kernel moves the slot's lanes to lanes ``[0, R)`` before the
    product (``_unpacked``), an exact permutation of lanes;
  * the one-hot contraction is three single-pass bfloat16 matmuls, exact
    as ``Precision.HIGHEST`` is (``segment_sum``);
  * a call may carry the output in: given ``carry``, the output aliases it
    and a tile's first visit loads the carried tile instead of zeros, so a
    mode's blocks can go through the kernel in consecutive pieces (a tile
    whose blocks straddle two calls sums across both, tiles a call never
    visits keep what they held).

VMEM budget per grid step (defaults BLOCK=512, ROW_TILE=128, R padded 128),
with three gathered operands (order 4):
  operands: 3 x 512 x 128 x 4B = 768 KiB, 1.5 MiB double-buffered
  prod + out tile (+ carried tile): (512x128 + 2 x 128x128) x 4B = 384 KiB
comfortably inside a v5e core's ~16 MiB VMEM.

The MXU work per step is twelve (128 x 128) @ (128 x 128) bfloat16 matmuls,
three for each 128 non-zeros: every dim hardware-aligned (multiples of 128
lanes and 16 bfloat16 sublanes).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANE = 128  # TPU lane width: rank is padded to a multiple of this


def _bf16_parts(x: Array) -> tuple[Array, Array, Array]:
    """Split float32 ``x`` into bfloat16 ``hi``, ``mid`` and ``lo`` with
    ``hi + mid + lo == x`` exactly, as ``Precision.HIGHEST`` splits an
    operand: ``hi`` is ``x`` with the low 16 bits of its significand
    cleared, exact in bfloat16 and exactly subtracted; ``mid`` is the same
    of what is left, and ``lo`` what is left after that, at most 8
    significant bits."""
    def head(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)

    hi = head(x)
    rest = x - hi
    mid = head(rest)
    lo = rest - mid
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            lo.astype(jnp.bfloat16))


def segment_sum(local: Array, prod: Array, row_tile: int) -> Array:
    """``out[m] = sum of prod[n] over the n with local[n] == m``, an
    ``(row_tile, R)`` float32 tile: the one-hot segment matrix
    ``S[m, n] = (local[n] == m)`` contracted with ``prod`` on the MXU.

    Three single-pass bfloat16 matmuls, each accumulated in float32, do what
    ``Precision.HIGHEST`` does on float32 operands, without its three
    passes that multiply zeros: HIGHEST splits both operands into bfloat16
    parts and sums six of their products, but ``S`` is 0/1, exact in
    bfloat16, so its middle and low parts are zero.  ``prod``'s parts hold
    its 24-bit significand exactly and each product of a part with 0 or 1
    is exact, so ``S @ hi + S @ mid + S @ lo`` is HIGHEST's arithmetic: only
    the order of the float32 accumulation differs.

    The block is contracted ``LANE`` non-zeros at a time, the MXU's depth,
    into one accumulator: the same products, with few enough values live
    at once that the body all but stops spilling them to VMEM.
    """
    out = None
    for start in range(0, local.shape[0], LANE):
        rows = local[start:start + LANE]
        iota = jax.lax.broadcasted_iota(jnp.int32, (row_tile, rows.shape[0]), 0)
        sel = (iota == rows[None, :]).astype(jnp.bfloat16)
        for part in _bf16_parts(prod[start:start + LANE]):
            term = jax.lax.dot(sel, part, preferred_element_type=jnp.float32)
            out = term if out is None else out + term
    return out


def _unpacked(x: Array, slot: Array, width: int) -> Array:
    """Each row of ``x`` (``(BLOCK, RP)`` float32, ``RP // width`` slots of
    ``width`` lanes a row) with its slot ``slot`` (``(BLOCK, 1)``) moved to
    lanes ``[0, width)``: one static lane roll for each slot past the
    first, chosen row by row.  A permutation of lanes, so lanes
    ``[0, width)`` are the slot's values to the bit; the lanes past them
    hold the row's other slots."""
    rp = x.shape[-1]
    out = x
    for s in range(1, rp // width):
        out = jnp.where(slot == s, pltpu.roll(x, rp - s * width, 1), out)
    return out


def _kernel(tile_map_ref, rows_ref, vals_ref, *refs, row_tile: int,
            packed: tuple[bool, ...], slot_width: Optional[int],
            carried: bool):
    num_operands = len(packed)
    operand_refs = refs[:num_operands]
    slot_refs = iter(refs[num_operands:num_operands + sum(packed)])
    carry_ref = refs[num_operands + sum(packed)] if carried else None
    out_ref = refs[-1]
    b = pl.program_id(0)
    tile = tile_map_ref[b]
    prev_tile = tile_map_ref[jnp.maximum(b - 1, 0)]
    is_first_visit = jnp.logical_or(b == 0, tile != prev_tile)

    @pl.when(is_first_visit)
    def _init():
        if carried:
            out_ref[...] = carry_ref[...]
        else:
            out_ref[...] = jnp.zeros_like(out_ref)

    def operand(i: int) -> Array:
        x = operand_refs[i][0].astype(jnp.float32)
        if packed[i]:
            x = _unpacked(x, next(slot_refs)[0, 0][:, None], slot_width)
        return x

    # fused Khatri-Rao partial product, (BLOCK, R): (vals * op0) * (op1 *
    # op2 * ...), which at order 3 is (vals * brows) * crows
    prod = vals_ref[0, 0][:, None].astype(jnp.float32) * operand(0)
    if num_operands > 1:
        others = operand(1)
        for i in range(2, num_operands):
            others = others * operand(i)
        prod = prod * others
    # collisions inside the block are summed by the MXU
    local = rows_ref[0, 0] - tile * row_tile  # (BLOCK,), in [0, row_tile)
    out_ref[...] += segment_sum(local, prod, row_tile)


def mttkrp_pallas_call(
    rows: Array,        # (nblocks, 1, BLOCK) int32, tile-aligned sorted rows
    vals: Array,        # (nblocks, 1, BLOCK)
    operands: Sequence[Array],  # each (nblocks, BLOCK, RP): gathered factor
                                #  rows, one array per other mode
    block_tile: Array,  # (nblocks,) int32 non-decreasing block -> tile map
    *,
    num_row_tiles: int,
    row_tile: int,
    interpret: bool,
    carry: Optional[Array] = None,  # (num_row_tiles * row_tile, RP) float32
                                    #  output of an earlier call, aliased
    slots: Optional[Sequence[Optional[Array]]] = None,
                        # per operand, None or (nblocks, 1, BLOCK) int32:
                        #  the slot of each row of a packed operand
    slot_width: Optional[int] = None,  # the lanes of a slot: the rank
) -> Array:
    """``out[r] = sum over the non-zeros of row r of vals * op0 * op1 ...``,
    a ``(num_row_tiles * row_tile, RP)`` float32 array.  Without ``carry``
    every visited tile starts from zeros; with it the output is ``carry``'s
    buffer, each visited tile starts from its carried value and the tiles
    not visited are ``carry``'s.

    An operand with a slot stream is packed: each of its rows holds
    ``RP // slot_width`` slots of ``slot_width`` lanes, and its factor row
    is slot ``slots[i]``.  The product is formed from that slot, moved to
    lanes ``[0, slot_width)``; the output's lanes from ``slot_width`` on
    then hold sums of other slots' products, for the caller to slice off."""
    nblocks, _, block = rows.shape
    rp = operands[0].shape[-1]
    if rp % LANE:
        raise ValueError(f"rank must be padded to {LANE}, got {rp}")
    slots = [None] * len(operands) if slots is None else list(slots)
    packed = tuple(slot is not None for slot in slots)

    # (1, 1, block): the last two block dims equal the array's (1, block),
    # which the TPU lowering requires of a 1-row block
    index_spec = pl.BlockSpec((1, 1, block), lambda b, tm: (b, 0, 0))
    operand_spec = pl.BlockSpec((1, block, rp), lambda b, tm: (b, 0, 0))
    tile_spec = pl.BlockSpec((row_tile, rp), lambda b, tm: (tm[b], 0))
    slot_streams = [slot for slot in slots if slot is not None]
    in_specs = ([index_spec, index_spec] + [operand_spec] * len(operands)
                + [index_spec] * len(slot_streams))
    args = [block_tile, rows, vals, *operands, *slot_streams]
    aliases = {}
    if carry is not None:
        in_specs.append(tile_spec)
        aliases = {len(args): 0}  # counted with the scalar-prefetch operand
        args.append(carry)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=tile_spec,
    )
    return pl.pallas_call(
        functools.partial(_kernel, row_tile=row_tile, packed=packed,
                          slot_width=slot_width,
                          carried=carry is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_row_tiles * row_tile, rp), jnp.float32),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # sequential: accumulation
        ),
        interpret=interpret,
    )(*args)
