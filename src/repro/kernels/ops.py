"""Public jit'd wrappers around the Pallas kernels.

Each wrapper handles the shape plumbing the kernel requires (rank padding to
the 128-lane width, block reshapes, gathers of factor rows) and slices the
result back to logical shapes.  ``interpret`` defaults to *backend detection*
(:func:`default_interpret`): on a TPU the kernels compile, on the CPU backend
(tests, rehearsals) they run in interpret mode, and any other backend is an
error.  Pass ``interpret=False`` to compile for a described TPU from a CPU
host (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.csf import CSF
from repro.core.linearized import Linearized
from repro.obs.metrics import get_registry

from .linearized_pallas import mttkrp_lin_pallas_call
from .mttkrp_pallas import LANE, mttkrp_pallas_call
from .syrk_pallas import syrk_pallas_call

Array = jax.Array


def default_interpret() -> bool:
    """False on a TPU (the kernels compile), True on the CPU backend (the
    Pallas interpreter).  Any other backend raises: the kernels have no
    compiled path there, and interpreting them would hide the device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile only for a TPU and are interpreted only on "
        f"the CPU backend; the default backend is {backend!r}")


def _pad_lanes(a: Array) -> Array:
    r = a.shape[-1]
    rp = -(-r // LANE) * LANE
    if rp == r:
        return a
    pad = [(0, 0)] * (a.ndim - 1) + [(0, rp - r)]
    return jnp.pad(a, pad)


def _padded(factor: Array) -> Array:
    """``factor`` padded to the lane width, for its rows to be gathered
    from.  The barrier keeps the pad before the gather: XLA would otherwise
    move it after, and on a TPU an (nnz, R) array is laid out in 128-lane
    tiles anyway: the gather and its padded copy would be two nnz x 128
    buffers instead of one (at yelp's scale, 4.2 GB each)."""
    return jax.lax.optimization_barrier(_pad_lanes(factor))


# The most bytes a factor's lane-padded table may take for XLA to keep it in
# VMEM beside everything else a sweep holds there (a v5e core has 128 MiB).
# A factor whose padded table is larger is gathered from a table packed
# ``LANE // rank`` rows to a lane row (``_slots_per_row``).
VMEM_TABLE_BYTES = 64 * 2**20


def _slots_per_row(factor: Array) -> int:
    """How many of ``factor``'s rows share a lane row of the table its rows
    are gathered from: ``LANE // rank`` where its lane-padded table exceeds
    ``VMEM_TABLE_BYTES`` and at least two rows fit a lane row, else 1 (the
    lane-padded table, ``_padded``)."""
    rows, rank = factor.shape
    k = LANE // rank
    if k >= 2 and rows * LANE * factor.dtype.itemsize > VMEM_TABLE_BYTES:
        return k
    return 1


def _packed(factor: Array, k: int) -> Array:
    """``factor``'s rows packed ``k`` to a lane row: row ``w`` in lane row
    ``w // k``, lanes ``[w % k * R, w % k * R + R)``, every other lane zero.
    Behind the same barrier as ``_padded``."""
    rows, rank = factor.shape
    n = -(-rows // k)
    table = jnp.pad(factor, ((0, n * k - rows), (0, 0))).reshape(n, k * rank)
    return jax.lax.optimization_barrier(_pad_lanes(table))


def _gather_padded(factor: Array, ids: Array) -> Array:
    """Rows ``factor[ids]`` padded to the lane width, gathered from the
    padded factor (``_padded``)."""
    return _padded(factor)[ids]


def _block_ranges(num_blocks: int, order: int) -> list[tuple[int, int]]:
    """The consecutive ranges of a mode's blocks that go through the MTTKRP
    kernel one call each: ``ceil((order - 1) / 2)`` of equal size, the last
    one shorter.  One range at order 3; two at orders 4 and 5, so that a
    range's ``order - 1`` gathered operands take no more HBM than two of
    the whole mode's."""
    pieces = -(-(order - 1) // 2)
    size = -(-num_blocks // pieces)
    return [(lo, min(lo + size, num_blocks))
            for lo in range(0, num_blocks, size)]


@partial(jax.jit, static_argnames=("interpret",))
def mttkrp(csf: CSF, factors: Sequence[Array], *,
           interpret: Optional[bool] = None) -> Array:
    """MTTKRP for the mode ``csf`` was built for.  Returns (num_rows, R).

    The factor-row gathers stay in XLA (HBM-bandwidth work XLA does well);
    the kernel fuses the Khatri-Rao multiply of every gathered operand and
    the conflict-resolving one-hot matmul.  From order 4 on, the rows of the
    factors past the first two are gathered under the scope
    ``gather/khatri_rao``, and the mode's blocks go through the kernel in
    consecutive ranges (``_block_ranges``), the output carried from call to
    call: each range's gathered rows are live only for its own call.

    A factor too large for its lane-padded table to stay in VMEM
    (``_slots_per_row``) is gathered, under the scope ``packed``, from a
    table packed ``k`` rows to a lane row at ``ids // k``, and the kernel
    moves slot ``ids % k`` of each gathered row to lanes ``[0, R)``.  The
    gauge ``mttkrp.packed_gathers.mode{n}`` holds, once mode n is traced,
    how many of its gathered operands are read from a packed table.
    """
    if interpret is None:
        interpret = default_interpret()
    rank = factors[0].shape[1]
    om = csf.other_modes
    nblocks, block = csf.num_blocks, csf.block
    ranges = _block_ranges(nblocks, csf.order)
    whole = len(ranges) == 1
    slots_per_row = [_slots_per_row(factors[m]) for m in om]
    get_registry().gauge(f"mttkrp.packed_gathers.mode{csf.mode}").set(
        sum(k > 1 for k in slots_per_row))
    tables = {}  # each factor's table built once a mode, on its first gather

    def gather(i: int, ids: Array) -> tuple[Array, Optional[Array]]:
        """Operand ``i``'s gathered rows and, read from a packed table,
        the slot of each, ``(nblocks, 1, block)``."""
        k = slots_per_row[i]
        if k == 1:
            if i not in tables:
                tables[i] = _padded(factors[om[i]])
            return tables[i][ids[:, i]], None
        with jax.named_scope("packed"):
            if i not in tables:
                tables[i] = _packed(factors[om[i]], k)
            return (tables[i][ids[:, i] // k],
                    (ids[:, i] % k).reshape(-1, 1, block))

    out = None
    for lo, hi in ranges:
        ids = csf.other_ids if whole else csf.other_ids[lo * block:hi * block]
        with jax.named_scope("gather"):
            gathered = [gather(i, ids) for i in range(2)]
            with jax.named_scope("khatri_rao"):
                gathered += [gather(i, ids) for i in range(2, len(om))]
        operands, slots = zip(*gathered)
        rp = operands[0].shape[-1]
        if out is None and not whole:
            with jax.named_scope("kernel"):
                out = jnp.zeros((csf.num_row_tiles * csf.row_tile, rp),
                                jnp.float32)
        # the innermost scope names the custom call, in the compiled program
        # and in a trace: keep it the kernel's name
        with jax.named_scope("kernel"), jax.named_scope("mttkrp"):
            rows = csf.row_ids.reshape(nblocks, 1, block)
            vals = csf.vals.reshape(nblocks, 1, block)
            tiles = csf.block_tile
            if not whole:
                rows, vals, tiles = rows[lo:hi], vals[lo:hi], tiles[lo:hi]
            out = mttkrp_pallas_call(
                rows, vals,
                [op.reshape(hi - lo, block, rp) for op in operands],
                tiles,
                num_row_tiles=csf.num_row_tiles,
                row_tile=csf.row_tile,
                interpret=interpret,
                carry=out,
                slots=slots,
                slot_width=rank,
            )
    return out[: csf.num_rows, :rank].astype(factors[0].dtype)


@partial(jax.jit, static_argnames=("interpret",))
def ttmc(csf: CSF, factors: Sequence[Array], *,
         interpret: Optional[bool] = None) -> Array:
    """Chain-of-modes TTMc for the mode ``csf`` was built for.

    Returns (num_rows, prod_{m != mode} R_m).  The kernel is the MTTKRP
    one-hot segment-matmul reused verbatim: the row-wise Kronecker chain of
    the other modes' factor rows is formed XLA-side (it is just a reshaped
    outer product — HBM-bandwidth work, like the factor gathers) and fed in
    as the kernel's one operand, so the fused ``vals * kron`` multiply and
    the conflict-resolving one-hot matmul run unchanged at the wider
    Kronecker rank.
    """
    if interpret is None:
        interpret = default_interpret()
    from repro.core.ttmc import kron_chain  # one column-order convention

    kron = kron_chain([factors[m][csf.other_ids[:, i]]
                       for i, m in enumerate(csf.other_modes)])
    width = kron.shape[-1]
    kron = _pad_lanes(kron)

    nblocks, block = csf.num_blocks, csf.block
    rp = kron.shape[-1]
    out = mttkrp_pallas_call(
        csf.row_ids.reshape(nblocks, 1, block),
        csf.vals.reshape(nblocks, 1, block),
        [kron.reshape(nblocks, block, rp)],
        csf.block_tile,
        num_row_tiles=csf.num_row_tiles,
        row_tile=csf.row_tile,
        interpret=interpret,
    )
    return out[: csf.num_rows, :width].astype(factors[0].dtype)


@partial(jax.jit, static_argnames=("mode", "interpret"))
def mttkrp_lin(lin: Linearized, factors: Sequence[Array], mode: int, *,
               interpret: Optional[bool] = None) -> Array:
    """MTTKRP for any mode from the single linearized workspace.

    On the sort mode the stream is already ordered and tile-aligned by the
    output row, so the Pallas one-hot segment-matmul kernel applies with the
    row decode moved *inside* the kernel (shift/mask on the packed hi/lo
    words); the factor-row gathers — which need the other modes' decoded
    coordinates — stay XLA-side like the CSF path.  On non-sort modes there
    is no block -> output-tile structure to exploit, so this follows ALTO's
    recompute path: decode + scatter-add in plain jnp (the pure reference
    impl), still from the same resident buffer with no re-sort.
    """
    if interpret is None:
        interpret = default_interpret()
    if mode != lin.sort_mode:  # static: sort_mode is pytree aux data
        from repro.core.mttkrp import mttkrp_linearized
        return mttkrp_linearized(lin, factors, mode)
    rank = factors[0].shape[1]
    om = [m for m in range(lin.order) if m != mode]
    with jax.named_scope("gather"):
        brows = _gather_padded(factors[om[0]], lin.decode(om[0]))
        crows = _gather_padded(factors[om[1]], lin.decode(om[1]))
        with jax.named_scope("khatri_rao"):
            for m in om[2:]:
                crows = crows * _gather_padded(factors[m], lin.decode(m))

    nblocks, block = lin.num_blocks, lin.block
    rp = brows.shape[-1]
    with jax.named_scope("kernel"), jax.named_scope("mttkrp_lin"):
        out = mttkrp_lin_pallas_call(
            lin.hi.reshape(nblocks, 1, block),
            lin.lo.reshape(nblocks, 1, block),
            lin.vals.reshape(nblocks, 1, block),
            brows.reshape(nblocks, block, rp),
            crows.reshape(nblocks, block, rp),
            lin.block_tile,
            num_row_tiles=lin.num_row_tiles,
            row_tile=lin.row_tile,
            offset=lin.offsets[mode],
            width=lin.widths[mode],
            interpret=interpret,
        )
    return out[: lin.dims[mode], :rank].astype(factors[0].dtype)


@partial(jax.jit, static_argnames=("mode", "interpret"))
def ttmc_lin(lin: Linearized, factors: Sequence[Array], mode: int, *,
             interpret: Optional[bool] = None) -> Array:
    """Chain-of-modes TTMc from the linearized workspace (cf. ``ttmc``).

    Sort mode: the Kronecker chain of the other modes' factor rows is formed
    XLA-side and fed to the in-kernel-decode kernel with an all-ones second
    operand.  Non-sort modes fall back to the jnp decode + scatter reference.
    """
    if interpret is None:
        interpret = default_interpret()
    from repro.core.ttmc import kron_chain, ttmc_linearized
    if mode != lin.sort_mode:
        return ttmc_linearized(lin, factors, mode)

    om = [m for m in range(lin.order) if m != mode]
    kron = kron_chain([factors[m][lin.decode(m)] for m in om])
    width = kron.shape[-1]
    kron = _pad_lanes(kron)

    nblocks, block = lin.num_blocks, lin.block
    rp = kron.shape[-1]
    out = mttkrp_lin_pallas_call(
        lin.hi.reshape(nblocks, 1, block),
        lin.lo.reshape(nblocks, 1, block),
        lin.vals.reshape(nblocks, 1, block),
        kron.reshape(nblocks, block, rp),
        jnp.ones((nblocks, block, rp), dtype=kron.dtype),
        lin.block_tile,
        num_row_tiles=lin.num_row_tiles,
        row_tile=lin.row_tile,
        offset=lin.offsets[mode],
        width=lin.widths[mode],
        interpret=interpret,
    )
    return out[: lin.dims[mode], :width].astype(factors[0].dtype)


@partial(jax.jit, static_argnames=("blk", "interpret"))
def syrk(a: Array, *, blk: int = 512,
         interpret: Optional[bool] = None) -> Array:
    """G = A^T A via the blocked Pallas kernel.  Returns (R, R)."""
    if interpret is None:
        interpret = default_interpret()
    rows, rank = a.shape
    ap = _pad_lanes(a)
    rows_p = -(-rows // blk) * blk
    if rows_p != rows:
        ap = jnp.pad(ap, ((0, rows_p - rows), (0, 0)))
    g = syrk_pallas_call(ap, blk=blk, interpret=interpret)
    return g[:rank, :rank].astype(a.dtype)
