"""Published chip peaks and the compulsory work of one MTTKRP.

The least time of a kernel is the larger of its compulsory bytes over the
chip's HBM bandwidth and its operations over the chip's peak rate.  The
bytes and operations are those the algorithm cannot avoid, whatever
implements it: every index and value read once, every factor read once,
the output written once, and ``order`` operations per non-zero and rank
column (``order - 1`` multiplies and one add).  Lane padding, workspace
padding and how rows are gathered do not enter, so a change of
implementation never moves the yardstick it is scored against.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

INDEX_BYTES = 4   # int32 coordinates
VALUE_BYTES = 4   # float32 values and factors


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float    # FLOP/s
    hbm_bw: float   # HBM bytes/s
    hbm_bytes: float
    source: str


# keyed by ``jax.Device.device_kind``; a kind that is not here is an error
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                         source="Google Cloud documentation, 'TPU v5e'"),
}


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {tuple(PEAKS)}") from None


def mttkrp_bytes(dims: Sequence[int], nnz: int, rank: int) -> int:
    """Compulsory HBM bytes of one MTTKRP of one mode: the non-zeros'
    indices and values read once, the other factors read once and the
    output written once (together every factor once)."""
    order = len(dims)
    return (nnz * (order * INDEX_BYTES + VALUE_BYTES)
            + sum(int(d) for d in dims) * rank * VALUE_BYTES)


def mttkrp_flops(dims: Sequence[int], nnz: int, rank: int) -> int:
    """Operations of one MTTKRP of one mode: ``order - 1`` multiplies and
    one add per non-zero and rank column."""
    return len(dims) * nnz * rank


def sweep_bytes(dims: Sequence[int], nnz: int, rank: int) -> int:
    """Compulsory bytes of one MTTKRP of every mode."""
    return len(dims) * mttkrp_bytes(dims, nnz, rank)


def sweep_flops(dims: Sequence[int], nnz: int, rank: int) -> int:
    return len(dims) * mttkrp_flops(dims, nnz, rank)


def least_seconds(flops: float, nbytes: float, peaks: Peaks) -> float:
    """The larger of the compute bound and the bandwidth bound."""
    return max(flops / peaks.flops, nbytes / peaks.hbm_bw)
