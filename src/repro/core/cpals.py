"""CP-ALS driver — Algorithm 1 of the paper, faithfully.

Per iteration, for each mode n (in order, 3rd-order shown; arbitrary order
supported):

    V      = hadamard_{m != n} (A_m^T A_m)          Mat A^TA (of other modes)
    M      = MTTKRP(X, factors, n)                  MTTKRP
    A_n    = M V^{-1}  (Cholesky)                   Inverse
    A_n, l = column-normalize(A_n)                  Mat norm  (max-norm on
                                                    iter 0, 2-norm after —
                                                    SPLATT's schedule)
    G_n    = A_n^T A_n
    fit    = 1 - ||X - X_hat|| / ||X||              CPD fit (via the
                                                    work-free inner-product
                                                    trick on the last mode)

The driver runs a python loop over iterations with a fused, jitted iteration
body; with ``timers=`` it instead calls one jitted function per routine and
accumulates wall-clock per routine — reproducing the paper's Table III
per-routine breakdown.  The pre-processing "Sort" stage (CSF build) is timed
under the same key the paper uses.

State is an explicit pytree (:class:`CPALSState`) so long decompositions can
be checkpointed/restored mid-run (see repro.checkpoint) — iteration index,
factors, lambda and previous fit fully determine the computation.
"""
from __future__ import annotations

import dataclasses
import time
from functools import lru_cache, partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.obs import trace as obs_trace

from .gram import (gram, hadamard_grams, solve_cholesky, solve_gram, normalize,
                   kruskal_fit)
from .coo import SparseTensor
from .csf import CSF, build_csf
from .mttkrp import mttkrp

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CPDecomp:
    """Result: X ~ sum_r lambda_r * outer(A_1[:,r], ..., A_N[:,r])."""

    factors: tuple[Array, ...]
    lmbda: Array
    fit: Array

    def tree_flatten(self):
        return (self.factors, self.lmbda, self.fit), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        factors, lmbda, fit = children
        return cls(factors=tuple(factors), lmbda=lmbda, fit=fit)

    @property
    def rank(self) -> int:
        return int(self.factors[0].shape[1])

    def values_at(self, inds: Array) -> Array:
        """Reconstructed entries at coordinate list (n, order)."""
        prod = jnp.broadcast_to(
            self.lmbda[None, :], (inds.shape[0], self.lmbda.shape[0])
        )
        for m, a in enumerate(self.factors):
            prod = prod * a[inds[:, m]]
        return jnp.sum(prod, axis=1)

    def to_dense(self, dims: Sequence[int] | None = None) -> Array:
        """Densify (tests only)."""
        order = len(self.factors)
        letters = "abcdefgh"[:order]
        eq = ",".join(f"{c}r" for c in letters) + ",r->" + letters
        return jnp.einsum(eq, *self.factors, self.lmbda)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CPALSState:
    """Checkpointable mid-run state of the ALS loop."""

    factors: tuple[Array, ...]
    lmbda: Array
    fit: Array
    fit_prev: Array
    iteration: Array  # int32 scalar

    def tree_flatten(self):
        return (
            self.factors,
            self.lmbda,
            self.fit,
            self.fit_prev,
            self.iteration,
        ), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        factors, lmbda, fit, fit_prev, iteration = children
        return cls(tuple(factors), lmbda, fit, fit_prev, iteration)


# ---------------------------------------------------------------------------
# workspace: per-mode prebuilt layouts (the paper's "Sort" stage)
# ---------------------------------------------------------------------------


def resolve_plan(t: SparseTensor, impl: str, plan, *, rank: int = 16,
                 block: int = 512, row_tile: int = 128):
    """Resolve the (impl=, plan=) pair every driver accepts into a DecompPlan.

    ``plan`` wins when given; otherwise the planner runs with ``impl`` as the
    policy ("auto" selects per mode from stats; a concrete name pins it with
    the stats pass skipped — the legacy zero-overhead path)."""
    if plan is not None:
        return plan
    from repro.plan import plan_decomposition

    return plan_decomposition(t, impl, rank=rank, block=block,
                              row_tile=row_tile,
                              with_stats=impl == "auto")


def build_workspace(
    t: SparseTensor,
    plan,
    *,
    block: int = 512,
    row_tile: int = 128,
):
    """One prebuilt structure per mode (SPLATT ALLMODE policy).

    ``plan`` is a :class:`repro.plan.DecompPlan` (each mode gets the layout
    its planned impl consumes: the unified CSF workspace, the mode-agnostic
    linearized workspace, or raw COO) or, for backwards compatibility, an
    impl-name string.  All ``"lin"`` modes share ONE
    :class:`~repro.core.linearized.Linearized` object — the format's whole
    point is a single resident buffer (and a single sort) for every mode."""
    if isinstance(plan, str):
        from repro.plan import plan_decomposition

        plan = plan_decomposition(t, plan, block=block, row_tile=row_tile,
                                  with_stats=plan == "auto")
    lin = None
    ws = []
    for p in plan.modes:
        if p.layout == "csf":
            ws.append(build_csf(t, p.mode, block=p.block,
                                row_tile=p.row_tile))
        elif p.layout == "lin":
            if lin is None:
                from .linearized import build_linearized

                lin = build_linearized(t, block=p.block,
                                       row_tile=p.row_tile)
            ws.append(lin)
        else:
            ws.append(t)
    return ws


# ---------------------------------------------------------------------------
# single-mode update + fused iteration
# ---------------------------------------------------------------------------


def init_factors(
    dims: Sequence[int], rank: int, key: Array, dtype=jnp.float32
) -> tuple[Array, ...]:
    keys = jax.random.split(key, len(dims))
    return tuple(
        jax.random.uniform(k, (int(d), rank), dtype=dtype)
        for k, d in zip(keys, dims)
    )


def _mode_update(ws_n, factors, grams, mode: int, impl: str, norm_kind: str):
    m_mat = mttkrp(ws_n, factors, mode, impl=impl)
    factors, grams, lam, _ = _mode_epilogue(
        m_mat, tuple(factors), tuple(grams),
        jnp.array(0.0, dtype=factors[0].dtype),
        mode=mode, norm_kind=norm_kind, with_fit=False)
    return factors[mode], grams[mode], lam, m_mat


def _mode_epilogue(m_mat, factors, grams, norm_x_sq, *, mode: int,
                   norm_kind: str, with_fit: bool):
    """Everything after one mode's MTTKRP, as one traceable function: the
    gram-hadamard, the Cholesky solve, the column normalization, the gram
    refresh — and, when ``with_fit`` (the last mode), the work-free fit.

    This is the chain the per-routine driver used to run as five separate
    jitted calls with a host sync between each; fused under one jit the
    intermediates (V, the un-normalized A_n, the column norms) never leave
    the device and XLA fuses the small matrix ops end-to-end.  Returns the
    *full* updated ``(factors, grams, lam, fit)`` tuples so the factor
    buffers can be donated across calls (see :func:`fused_mode_epilogue`)."""
    with jax.named_scope(f"epilogue/mode{mode}"):
        v = hadamard_grams(grams, mode)
        # solve_gram, not solve_cholesky: inside the fused trace the GEMM
        # formulation is what makes the collapsed chain beat the
        # per-routine path on CPU (cho_solve with I right-hand sides is
        # scalar there)
        a_new = solve_gram(m_mat, v)
        a_new, lam = normalize(a_new, kind=norm_kind)
        g_new = gram(a_new)
        factors = tuple(a_new if m == mode else f
                        for m, f in enumerate(factors))
        grams = tuple(g_new if m == mode else g
                      for m, g in enumerate(grams))
        if with_fit:
            fit = kruskal_fit(norm_x_sq, lam, grams, m_mat, factors[-1])
        else:
            # No fit was computed: return NaN, not a fake 0.0 that
            # downstream reports would read as "converged to fit 0".  The
            # ALS loop keeps the last *computed* fit (previous iteration /
            # restored state) instead.
            fit = jnp.array(jnp.nan, dtype=factors[0].dtype)
    return factors, grams, lam, fit


def donate_buffers() -> bool:
    """Whether factor/gram buffer donation is worth requesting: jax only
    implements input-output aliasing on TPU/GPU — on CPU it is ignored with
    a warning per call site, so we don't ask."""
    return jax.default_backend() in ("tpu", "gpu")


@lru_cache(maxsize=None)
def _fused_epilogue_jit(donate: bool):
    return jax.jit(
        _mode_epilogue,
        static_argnames=("mode", "norm_kind", "with_fit"),
        donate_argnums=(1, 2) if donate else ())


def fused_mode_epilogue(m_mat, factors, grams, norm_x_sq, *, mode: int,
                        norm_kind: str, with_fit: bool = False,
                        donate: Optional[bool] = None):
    """One jitted call for a mode's whole post-MTTKRP update.

    ``donate`` (default: backend-resolved — :func:`donate_buffers`) hands
    the incoming factor/gram buffers to XLA for in-place reuse; callers must
    treat the inputs as consumed and keep only the returned tuples."""
    if donate is None:
        donate = donate_buffers()
    return _fused_epilogue_jit(donate)(
        m_mat, tuple(factors), tuple(grams), norm_x_sq,
        mode=mode, norm_kind=norm_kind, with_fit=with_fit)


def _iteration_impl(ws, factors, grams, norm_x_sq, *, impls, norm_kind,
                    with_fit=True):
    factors = tuple(factors)
    grams = tuple(grams)
    lam = None
    fit = jnp.array(jnp.nan, dtype=factors[0].dtype)
    order = len(factors)
    for n in range(order):
        m_mat = mttkrp(ws[n], factors, n, impl=impls[n])
        factors, grams, lam, fit = _mode_epilogue(
            m_mat, factors, grams, norm_x_sq, mode=n, norm_kind=norm_kind,
            with_fit=with_fit and n == order - 1)
    return factors, grams, lam, fit


@lru_cache(maxsize=None)
def _iteration_jit(donate: bool):
    return jax.jit(
        _iteration_impl,
        static_argnames=("impls", "norm_kind", "with_fit"),
        donate_argnums=(1, 2) if donate else ())


def _iteration(ws, factors, grams, norm_x_sq, *, impls, norm_kind,
               with_fit=True, donate=False):
    """One fused ALS iteration; ``impls`` is the plan's per-mode impl tuple.

    ``donate=True`` (the method drivers pass :func:`donate_buffers`) donates
    the factor/gram buffers to the jitted body — zero-copy factor updates on
    TPU/GPU; the caller must drop its references to the inputs."""
    return _iteration_jit(bool(donate))(
        ws, tuple(factors), tuple(grams), norm_x_sq,
        impls=impls, norm_kind=norm_kind, with_fit=with_fit)


# ---------------------------------------------------------------------------
# timed per-routine path (paper Table III)
# ---------------------------------------------------------------------------

ROUTINES = ("sort", "mttkrp", "ata", "inverse", "norm", "fit")
# the fused path collapses ata/inverse/norm/fit into one jitted call, timed
# under a single key (bench_cpals_routines reports it as epilogue_s)
ROUTINES_FUSED = ("sort", "mttkrp", "epilogue")
# the routines that make up the per-mode post-MTTKRP chain — the "epilogue"
# subtotal the fused path is measured against
EPILOGUE_ROUTINES = ("ata", "inverse", "norm", "fit")


def _timed(timers, key, fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    out = jax.block_until_ready(out)
    timers[key] = timers.get(key, 0.0) + (time.perf_counter() - t0)
    return out


@partial(jax.jit, static_argnames=("mode", "impl"))
def _jit_mttkrp(ws_n, factors, *, mode, impl):
    return mttkrp(ws_n, factors, mode, impl=impl)


@partial(jax.jit, static_argnames=("mode",))
def _jit_hadamard(grams, *, mode):
    return hadamard_grams(grams, mode)


_jit_solve = jax.jit(solve_cholesky)
_jit_gram = jax.jit(gram)
_jit_normalize = jax.jit(normalize, static_argnames=("kind",))
_jit_fit = jax.jit(kruskal_fit)


def _iteration_timed(ws, factors, grams, norm_x_sq, timers, *, impls,
                     norm_kind, with_fit=True, fused=False):
    """Per-routine timed iteration (paper Table III).

    ``fused=False`` times each routine as its own jitted call with a host
    sync in between — the historical breakdown.  ``fused=True`` times the
    MTTKRP per mode and the whole post-MTTKRP chain as ONE jitted
    ``fused_mode_epilogue`` call under the ``"epilogue"`` key — what the
    fused path actually executes, so the two variants' timer totals are the
    honest before/after of the fusion."""
    if fused:
        factors = tuple(factors)
        grams = tuple(grams)
        lam = None
        fit = jnp.array(jnp.nan, dtype=factors[0].dtype)
        order = len(factors)
        for n in range(order):
            with obs_trace.span("mttkrp", mode=n, impl=impls[n]):
                m_mat = _timed(timers, "mttkrp", _jit_mttkrp, ws[n], factors,
                               mode=n, impl=impls[n])
            with obs_trace.span("epilogue", mode=n):
                factors, grams, lam, fit = _timed(
                    timers, "epilogue", fused_mode_epilogue, m_mat, factors,
                    grams, norm_x_sq, mode=n, norm_kind=norm_kind,
                    with_fit=with_fit and n == order - 1)
        return factors, grams, lam, fit
    factors = list(factors)
    grams = list(grams)
    lam = m_last = None
    for n in range(len(factors)):
        with obs_trace.span("ata", mode=n):
            v = _timed(timers, "ata", _jit_hadamard, tuple(grams), mode=n)
        with obs_trace.span("mttkrp", mode=n, impl=impls[n]):
            m_mat = _timed(timers, "mttkrp", _jit_mttkrp, ws[n],
                           tuple(factors), mode=n, impl=impls[n])
        with obs_trace.span("inverse", mode=n):
            a_new = _timed(timers, "inverse", _jit_solve, m_mat, v)
        with obs_trace.span("norm", mode=n):
            a_new, lam = _timed(timers, "norm", _jit_normalize, a_new,
                                kind=norm_kind)
        with obs_trace.span("ata", mode=n):
            grams[n] = _timed(timers, "ata", _jit_gram, a_new)
        factors[n] = a_new
        m_last = m_mat
    if with_fit:
        with obs_trace.span("fit"):
            fit = _timed(timers, "fit", _jit_fit, norm_x_sq, lam,
                         tuple(grams), m_last, factors[-1])
    else:
        # skipped entirely: no fit work done, no "fit" seconds charged
        fit = jnp.array(jnp.nan, dtype=factors[0].dtype)
    return tuple(factors), tuple(grams), lam, fit


# ---------------------------------------------------------------------------
# driver — the ALS loop itself lives behind the method registry
# (repro.methods.cp_als); this thin re-export keeps the historical
# ``repro.core.cp_als`` entry point working unchanged, with a once-per-
# process DeprecationWarning pointing at the repro.api front door.
# ---------------------------------------------------------------------------

_warned_legacy = False


def _warn_legacy_entry() -> None:
    global _warned_legacy
    if not _warned_legacy:
        import warnings

        warnings.warn(
            "repro.core.cp_als is a legacy entry point; new code should go "
            "through repro.api (Session / run(RunConfig)) or "
            "repro.methods.fit(..., method='cp_als')",
            DeprecationWarning, stacklevel=3)
        _warned_legacy = True


def cp_als(t, rank: int, **kwargs) -> CPDecomp:
    """Run CP-ALS per Algorithm 1 (see :func:`repro.methods.cp_als.cp_als`,
    which owns the iteration loop behind the decomposition-method registry).

    .. deprecated:: use :func:`repro.api.run` / ``repro.methods.fit`` —
       this wrapper stays for the historical call sites and warns once per
       process.

    Lazy import: ``repro.methods`` imports this module for the iteration
    machinery (:func:`_iteration`, the state pytrees), so the dependency is
    only taken at call time."""
    from repro.methods.cp_als import cp_als as _cp_als

    _warn_legacy_entry()
    return _cp_als(t, rank, **kwargs)
