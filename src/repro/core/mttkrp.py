"""MTTKRP — matricized tensor times Khatri-Rao product — implementation registry.

The paper identifies MTTKRP as the critical kernel of CP-ALS (>90% of runtime,
Tab. III) and its performance study is, at heart, a study of MTTKRP
implementation strategies.  This module carries the registry of our analogues
as first-class :class:`ImplSpec` entries — each impl declares its input
layout, capabilities (sortedness requirement, order > 3 support, backend) and
a relative cost model, which is what lets the per-mode planner
(``repro.plan``) select an implementation from tensor statistics instead of a
hardcoded string:

==================  =========================================================
impl                what it reproduces
==================  =========================================================
``rowloop``         the paper's *Chapel-initial* code: one output row at a
                    time via dynamic slices (the slicing-overhead regime of
                    §V-D.1, Figs 2/3).  Benchmark-only — deliberately slow.
``gather_scatter``  flat vectorized gather + scatter-add with output-row
                    collisions.  The *mutex/atomic* regime of §V-D.2: XLA's
                    scatter-add serializes colliding rows exactly where
                    SPLATT's mutex pool would contend (YELP-like tensors).
``segment``         sorted-by-output-row segment-sum over the unified CSF
                    workspace — SPLATT's *no-lock* schedule (NELL-2 path):
                    row ownership is resolved by the sort, not by locks.
``pallas``          the TPU-native kernel (kernels/mttkrp_pallas.py): blocked
                    one-hot segment-matmul on the MXU; collisions inside a
                    block are reduced by the matmul itself.
``linearized``      ALTO-style mode-agnostic workspace (core/linearized.py):
                    one bit-packed sorted index serves every mode.  Sort mode
                    runs the no-lock segment reduction; other modes decode
                    coordinates (shift/mask) and scatter-add.  Pure jnp.
``linearized_pallas``  the linearized workspace on the TPU kernel
                    (kernels/linearized_pallas.py): the one-hot
                    segment-matmul with the coordinate decode moved *inside*
                    the kernel; non-sort modes fall back to the jnp decode.
``dense``           dense einsum oracle (tests only).
==================  =========================================================

All impls support arbitrary tensor order (the paper restricts to 3rd order;
SPLATT itself and our port support order >= 3 — this is one of the paper's
"future work" items implemented here).

Every CSF-consuming impl (``segment``, ``pallas``, ``gather_scatter``)
accepts the single unified :class:`~repro.core.csf.CSF` layout;
``gather_scatter``/``rowloop``/``dense`` also run straight off COO; the
``linearized*`` impls consume the mode-agnostic
:class:`~repro.core.linearized.Linearized` workspace (layout ``"lin"`` —
ONE buffer for the whole decomposition instead of one CSF per mode).

This table is kept in sync with ``docs/architecture.md`` ("The MTTKRP
implementation registry").
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from .coo import SparseTensor
from .csf import CSF
from .linearized import Linearized

Array = jax.Array

# ---------------------------------------------------------------------------
# Oracles / references
# ---------------------------------------------------------------------------


def mttkrp_dense(t: SparseTensor, factors: Sequence[Array], mode: int) -> Array:
    """Dense oracle: densify X and contract. Tests only (small tensors).

    M[i, r] = sum_{j,k,...} X[.., i, ..] * prod_{m != mode} A_m[idx_m, r]
    """
    if isinstance(t, CSF):
        raise TypeError("dense oracle consumes COO (SparseTensor), not CSF")
    dense = t.to_dense()
    order = t.order
    # Move `mode` axis first, contract the rest against the KRP.
    letters = "abcdefgh"[:order]
    out_l = letters[mode]
    terms = []
    for m in range(order):
        if m != mode:
            terms.append(f"{letters[m]}r")
    eq = f"{letters}," + ",".join(terms) + f"->{out_l}r"
    others = [factors[m] for m in range(order) if m != mode]
    return jnp.einsum(eq, dense, *others)


# ---------------------------------------------------------------------------
# rowloop — the deliberately naive "Chapel-initial" analogue (benchmarks only)
# ---------------------------------------------------------------------------


def mttkrp_rowloop(t: SparseTensor, factors: Sequence[Array], mode: int) -> Array:
    """One non-zero at a time with dynamic slices — the per-row-slice overhead
    regime the paper measures in §V-D.1.  O(nnz) sequential; benchmark-only."""
    if isinstance(t, CSF):
        raise TypeError("rowloop consumes COO (SparseTensor), not CSF")
    order = t.order
    rank = factors[0].shape[1]
    out = jnp.zeros((t.dims[mode], rank), dtype=factors[0].dtype)

    def body(n, out):
        row = t.inds[n, mode]
        acc = t.vals[n] * jnp.ones((rank,), dtype=out.dtype)
        for m in range(order):
            if m != mode:
                # dynamic row slice of the factor — the "slicing" analogue
                frow = jax.lax.dynamic_slice_in_dim(factors[m], t.inds[n, m], 1, 0)
                acc = acc * frow[0]
        cur = jax.lax.dynamic_slice_in_dim(out, row, 1, 0)
        return jax.lax.dynamic_update_slice_in_dim(out, cur + acc[None], row, 0)

    return jax.lax.fori_loop(0, t.padded_nnz, body, out)


# ---------------------------------------------------------------------------
# gather_scatter — vectorized, scatter-add collisions (COO or CSF input)
# ---------------------------------------------------------------------------


def _krp_rows(
    inds: Array, factors: Sequence[Array], mode: int, vals: Array
) -> Array:
    """prod[n, r] = vals[n] * prod_{m != mode} A_m[inds[n, m], r]; the
    factors past the first two (order > 3) under ``khatri_rao``."""
    others = [m for m in range(len(factors)) if m != mode]
    prod = vals[:, None].astype(factors[0].dtype)
    for m in others[:2]:
        prod = prod * factors[m][inds[:, m]]
    with jax.named_scope("khatri_rao"):
        for m in others[2:]:
            prod = prod * factors[m][inds[:, m]]
    return prod


def _krp_rows_csf(csf: CSF, factors: Sequence[Array]) -> Array:
    """The CSF-workspace analogue of :func:`_krp_rows` (padding entries carry
    value 0, so their products are exact zeros)."""
    prod = csf.vals[:, None].astype(factors[0].dtype)
    for i, m in enumerate(csf.other_modes[:2]):
        prod = prod * factors[m][csf.other_ids[:, i]]
    with jax.named_scope("khatri_rao"):
        for i, m in enumerate(csf.other_modes[2:], start=2):
            prod = prod * factors[m][csf.other_ids[:, i]]
    return prod


def mttkrp_gather_scatter(
    t, factors: Sequence[Array], mode: int
) -> Array:
    """Flat gather of factor rows, elementwise product, scatter-add.

    This is the "atomic variables" regime of the paper: colliding output rows
    are resolved by the scatter's serialized adds.  Fast when collisions are
    rare (NELL-2-like), degrades when one row is hot (YELP-like skew).

    Consumes either raw COO or the unified CSF workspace (whose padding
    entries carry value 0 and valid row ids, so they scatter exact zeros)."""
    if isinstance(t, CSF):
        if t.mode != mode:
            raise ValueError(f"CSF is built for mode {t.mode}, asked {mode}")
        with jax.named_scope("gather"):
            prod = _krp_rows_csf(t, factors)
        with jax.named_scope("kernel"):
            out = jnp.zeros((t.dims[mode], prod.shape[1]), dtype=prod.dtype)
            return out.at[t.row_ids].add(prod, mode="drop")
    rank = factors[0].shape[1]
    with jax.named_scope("gather"):
        prod = _krp_rows(t.inds, factors, mode, t.vals)
    with jax.named_scope("kernel"):
        out = jnp.zeros((t.dims[mode], rank), dtype=prod.dtype)
        return out.at[t.inds[:, mode]].add(prod, mode="drop")


# ---------------------------------------------------------------------------
# segment — sorted CSF, conflict-free segment reduction (no-lock path)
# ---------------------------------------------------------------------------


def mttkrp_segment(csf: CSF, factors: Sequence[Array],
                   mode: Optional[int] = None) -> Array:
    """Segment-sum over the per-mode sorted workspace.

    Sorting by output row is exactly SPLATT's no-lock schedule: each output
    row's contributions are contiguous, so a segment reduction needs no
    conflict resolution at all.  Padding entries carry value 0 and point at
    their tile's last real row, which keeps ``row_ids`` globally
    non-decreasing — the reduction keeps its ``indices_are_sorted`` fast
    path and the zeros contribute exactly nothing."""
    if not isinstance(csf, CSF):
        raise TypeError("segment impl needs a CSF workspace (build_csf(t, mode))")
    if mode is not None and csf.mode != mode:
        raise ValueError(f"CSF is built for mode {csf.mode}, asked {mode}")
    with jax.named_scope("gather"):
        prod = _krp_rows_csf(csf, factors)
    with jax.named_scope("kernel"):
        return jax.ops.segment_sum(prod, csf.row_ids,
                                   num_segments=csf.num_rows,
                                   indices_are_sorted=True)


def mttkrp_pallas(csf: CSF, factors: Sequence[Array],
                  mode: Optional[int] = None) -> Array:
    """The TPU kernel over the unified workspace (interpret mode off-TPU —
    resolved by ``kernels.ops.default_interpret``)."""
    if not isinstance(csf, CSF):
        raise TypeError("pallas impl needs a CSF workspace (build_csf(t, mode))")
    if mode is not None and csf.mode != mode:
        raise ValueError(f"CSF is built for mode {csf.mode}, asked {mode}")
    from repro.kernels import ops as kops  # local import: optional dep

    return kops.mttkrp(csf, factors)


# ---------------------------------------------------------------------------
# linearized — ALTO-style mode-agnostic bit-packed workspace (all modes from
# one resident buffer; see core/linearized.py for the format)
# ---------------------------------------------------------------------------


def _require_lin(ws) -> Linearized:
    if not isinstance(ws, Linearized):
        raise TypeError(
            "linearized impls need a Linearized workspace "
            "(build_linearized(t)); got " + type(ws).__name__)
    return ws


def mttkrp_linearized(ws, factors: Sequence[Array], mode: int) -> Array:
    """Pure-jnp reference over the linearized workspace — any mode, one buffer.

    Coordinates are recovered from the packed hi/lo words with static
    shifts/masks (``Linearized.decode``).  On the sort mode the stream is
    ordered by the output row (padding keeps it globally non-decreasing), so
    the no-lock ``segment_sum`` fast path applies; other modes take the
    scatter-add (mutex/atomic regime) — ALTO's recompute path, at zero extra
    resident memory and no re-sort."""
    lin = _require_lin(ws)
    with jax.named_scope("gather"):
        others = [m for m in range(lin.order) if m != mode]
        prod = lin.vals[:, None].astype(factors[0].dtype)
        for m in others[:2]:
            prod = prod * factors[m][lin.decode(m)]
        with jax.named_scope("khatri_rao"):
            for m in others[2:]:
                prod = prod * factors[m][lin.decode(m)]
    with jax.named_scope("kernel"):
        rows = lin.decode(mode)
        if mode == lin.sort_mode:
            return jax.ops.segment_sum(prod, rows,
                                       num_segments=lin.dims[mode],
                                       indices_are_sorted=True)
        out = jnp.zeros((lin.dims[mode], prod.shape[1]), dtype=prod.dtype)
        return out.at[rows].add(prod, mode="drop")


def mttkrp_linearized_pallas(ws, factors: Sequence[Array], mode: int) -> Array:
    """The linearized workspace on the TPU kernel: in-kernel shift/mask decode
    on the sort mode (kernels/linearized_pallas.py), jnp decode + scatter on
    the others (interpret mode off-TPU)."""
    lin = _require_lin(ws)
    from repro.kernels import ops as kops  # local import: optional dep

    return kops.mttkrp_lin(lin, factors, mode)


# ---------------------------------------------------------------------------
# cost models (relative per-iteration work; consumed by the planner)
# ---------------------------------------------------------------------------
#
# Each takes a duck-typed per-mode stats object (``repro.plan.ModeStats``:
# nnz, order, collision_rate, padding_overhead, ...) plus the CP rank and
# returns a unitless relative cost.  Constants encode the paper's regimes:
# scatter-adds serialize colliding rows (§V-D.2 mutex/atomic analogue) while
# the sorted paths pay the workspace's padding overhead instead; the MXU
# kernel turns conflict resolution into dense compute.

_SCATTER_SERIALIZATION = 8.0   # relative cost of a serialized colliding add
_MXU_SPEEDUP = 4.0             # dense one-hot matmul vs vector scatter


def _padded_nnz(stats) -> float:
    return stats.nnz / max(1e-9, 1.0 - stats.padding_overhead)


def _cost_gather_scatter(stats, rank: int) -> float:
    gather = stats.nnz * rank * (stats.order - 1)
    scatter = stats.nnz * rank * (
        1.0 + _SCATTER_SERIALIZATION * stats.collision_rate)
    return gather + scatter


def _cost_segment(stats, rank: int) -> float:
    # pays the tile-padding overhead, but the reduction is conflict-free
    return _padded_nnz(stats) * rank * stats.order


def _cost_pallas(stats, rank: int) -> float:
    return _padded_nnz(stats) * rank * stats.order / _MXU_SPEEDUP


def _cost_rowloop(stats, rank: int) -> float:
    return stats.nnz * rank * stats.order * 1e3  # sequential; never chosen


# Integer shift/mask work per coordinate decode, relative to a float
# gather+multiply unit of the models above.  Strictly positive: on predicted
# costs the linearized variants price as their sorted/scatter counterparts
# *plus* the decode, so they never displace a same-regime impl without a
# measured (calibrated) win — the single-resident-buffer advantage doesn't
# show up in flop-counting models.
_DECODE_DISCOUNT = 0.25


def _cost_decode(stats, rank: int) -> float:
    return _DECODE_DISCOUNT * stats.nnz * stats.order


def _cost_linearized(stats, rank: int) -> float:
    # the sort mode runs the segment (no-lock) regime, other modes the
    # scatter regime; scored per-mode we take whichever the mode's stats
    # favor, plus the decode
    base = min(_cost_segment(stats, rank), _cost_gather_scatter(stats, rank))
    return base + _cost_decode(stats, rank)


def _cost_linearized_pallas(stats, rank: int) -> float:
    base = min(_cost_pallas(stats, rank), _cost_gather_scatter(stats, rank))
    return base + _cost_decode(stats, rank)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ImplSpec:
    """One MTTKRP strategy and its declared capabilities.

    layout:     workspace the impl consumes — "csf" (unified CSF), "coo"
                (raw SparseTensor), or "any" (accepts both).
    needs_sorted: whether the impl relies on the workspace's row sort for
                correctness/conflict-freedom (the planner surfaces this as
                the paper's no-lock vs mutex/atomic distinction).
    backend:    "any", or a jax backend name ("tpu") the impl is *native* to;
                the auto policy only picks backend-specific impls on that
                backend (manual override still allowed anywhere).
    cost_model: (stats, rank) -> relative per-iteration cost, used by the
                auto policy's argmin.
    """

    name: str
    fn: Callable[..., Array]
    layout: str
    needs_sorted: bool
    supports_order_gt3: bool
    backend: str = "any"
    benchmark_only: bool = False
    oracle: bool = False
    cost_model: Optional[Callable[..., float]] = None


REGISTRY: dict[str, ImplSpec] = {}


def register_impl(spec: ImplSpec) -> ImplSpec:
    """Add (or replace) an implementation in the registry."""
    if spec.layout not in ("csf", "coo", "lin", "any"):
        raise ValueError(f"bad layout {spec.layout!r} for impl {spec.name!r}")
    REGISTRY[spec.name] = spec
    return spec


def get_impl(name: str, *, registry: Optional[dict] = None) -> ImplSpec:
    """Look up an :class:`ImplSpec` by name.

    ``registry`` defaults to the MTTKRP registry; other kernel families
    (``repro.core.ttmc``) pass their own table so the planner can score any
    registered sparse kernel with one code path."""
    registry = REGISTRY if registry is None else registry
    try:
        return registry[name]
    except KeyError:
        raise ValueError(
            f"unknown impl {name!r}; one of {tuple(registry)}") from None


def available_impls(*, order: int = 3, backend: Optional[str] = None,
                    include_benchmark: bool = False,
                    include_oracle: bool = False,
                    allow: Optional[Sequence[str]] = None,
                    registry: Optional[dict] = None) -> tuple[str, ...]:
    """Names of impls whose declared capabilities cover (order, backend).

    This is the planner's candidate filter: benchmark-only and oracle impls
    are excluded unless asked for, and backend-specific impls only qualify on
    their native backend.  ``registry`` selects the kernel family (MTTKRP by
    default; ``repro.core.ttmc.TTMC_REGISTRY`` for the Tucker chain).
    """
    registry = REGISTRY if registry is None else registry
    out = []
    for name, spec in registry.items():
        if allow is not None and name not in allow:
            continue
        if spec.benchmark_only and not include_benchmark:
            continue
        if spec.oracle and not include_oracle:
            continue
        if order > 3 and not spec.supports_order_gt3:
            continue
        if backend is not None and spec.backend not in ("any", backend):
            continue
        out.append(name)
    return tuple(out)


register_impl(ImplSpec(
    name="gather_scatter", fn=mttkrp_gather_scatter, layout="any",
    needs_sorted=False, supports_order_gt3=True,
    cost_model=_cost_gather_scatter))
register_impl(ImplSpec(
    name="segment", fn=mttkrp_segment, layout="csf",
    needs_sorted=True, supports_order_gt3=True,
    cost_model=_cost_segment))
register_impl(ImplSpec(
    name="pallas", fn=mttkrp_pallas, layout="csf",
    needs_sorted=True, supports_order_gt3=True, backend="tpu",
    cost_model=_cost_pallas))
register_impl(ImplSpec(
    name="linearized", fn=mttkrp_linearized, layout="lin",
    needs_sorted=True, supports_order_gt3=True,
    cost_model=_cost_linearized))
register_impl(ImplSpec(
    name="linearized_pallas", fn=mttkrp_linearized_pallas, layout="lin",
    needs_sorted=True, supports_order_gt3=True, backend="tpu",
    cost_model=_cost_linearized_pallas))
register_impl(ImplSpec(
    name="rowloop", fn=mttkrp_rowloop, layout="coo",
    needs_sorted=False, supports_order_gt3=True, benchmark_only=True,
    cost_model=_cost_rowloop))
register_impl(ImplSpec(
    name="dense", fn=mttkrp_dense, layout="coo",
    needs_sorted=False, supports_order_gt3=True, oracle=True))

IMPLS = tuple(REGISTRY)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------


def mttkrp(
    x,
    factors: Sequence[Array],
    mode: int,
    *,
    impl: str = "segment",
) -> Array:
    """Dispatch on the registry; ``x`` is a SparseTensor (COO impls) or the
    unified per-mode CSF workspace (``build_csf(t, mode)``).  ``impl="auto"``
    is resolved by the planner (``repro.plan.plan_decomposition``) before this
    point — pass a concrete name here.
    """
    if impl == "auto":
        raise ValueError(
            "impl='auto' is a planner policy; resolve it with "
            "repro.plan.plan_decomposition (or call cp_als(impl='auto')) "
            "and dispatch on the per-mode plan")
    spec = get_impl(impl)
    with jax.named_scope(f"mttkrp/mode{mode}"):
        return spec.fn(x, factors, mode)
