"""Step builder for the CP-ALS iteration of a decomposition workload: it
executes a per-mode :class:`repro.plan.DecompPlan`; see
``docs/architecture.md``.
"""
from __future__ import annotations


def make_cpals_step(plan):
    """One CP-ALS iteration executing a :class:`repro.plan.DecompPlan`.

    Returns ``(ws, factors, grams, norm_x_sq, norm_kind) -> (factors, grams,
    lmbda, fit)`` where ``ws`` is ``repro.core.build_workspace(t, plan)`` —
    the launch-layer entry the serving loop (and ad-hoc drivers) use, so the
    per-mode impl selection is decided once at plan time, not per step."""
    from repro.core.cpals import _iteration

    impls = plan.impls

    def cpals_step(ws, factors, grams, norm_x_sq, *, norm_kind="2"):
        return _iteration(ws, tuple(factors), tuple(grams), norm_x_sq,
                          impls=impls, norm_kind=norm_kind)

    return cpals_step
