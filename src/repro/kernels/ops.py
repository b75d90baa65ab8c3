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


def _gather_padded(factor: Array, ids: Array) -> Array:
    """Rows ``factor[ids]`` padded to the lane width, gathered from the
    padded factor.  XLA would otherwise move the pad after the gather, and
    on a TPU an (nnz, R) array is laid out in 128-lane tiles anyway: the
    gather and its padded copy would be two nnz x 128 buffers instead of
    one (at yelp's scale, 4.2 GB each)."""
    return jax.lax.optimization_barrier(_pad_lanes(factor))[ids]


@partial(jax.jit, static_argnames=("interpret",))
def mttkrp(csf: CSF, factors: Sequence[Array], *,
           interpret: Optional[bool] = None) -> Array:
    """MTTKRP for the mode ``csf`` was built for.  Returns (num_rows, R).

    The factor-row gathers stay in XLA (HBM-bandwidth work XLA does well);
    the kernel fuses the Khatri-Rao multiply and the conflict-resolving
    one-hot matmul.  For order > 3 the extra factors' rows are pre-multiplied
    into the second operand (associativity of the elementwise product),
    under the scope ``gather/khatri_rao``.
    """
    if interpret is None:
        interpret = default_interpret()
    rank = factors[0].shape[1]
    om = csf.other_modes
    with jax.named_scope("gather"):
        brows = _gather_padded(factors[om[0]], csf.other_ids[:, 0])
        crows = _gather_padded(factors[om[1]], csf.other_ids[:, 1])
        with jax.named_scope("khatri_rao"):
            for i in range(2, len(om)):
                crows = crows * _gather_padded(factors[om[i]],
                                               csf.other_ids[:, i])

    nblocks, block = csf.num_blocks, csf.block
    rp = brows.shape[-1]
    # the innermost scope names the custom call, in the compiled program
    # and in a trace: keep it the kernel's name
    with jax.named_scope("kernel"), jax.named_scope("mttkrp"):
        out = mttkrp_pallas_call(
            csf.row_ids.reshape(nblocks, 1, block),
            csf.vals.reshape(nblocks, 1, block),
            brows.reshape(nblocks, block, rp),
            crows.reshape(nblocks, block, rp),
            csf.block_tile,
            num_row_tiles=csf.num_row_tiles,
            row_tile=csf.row_tile,
            interpret=interpret,
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
    as the first operand with an all-ones second operand, so the fused
    ``vals * brows * crows`` multiply and the conflict-resolving one-hot
    matmul run unchanged at the wider Kronecker rank.
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
        kron.reshape(nblocks, block, rp),
        jnp.ones((nblocks, block, rp), dtype=kron.dtype),
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
