"""The shared driver iteration loop plumbing.

Every method driver used to carry its own copy of the same block::

    t0 = time.perf_counter()
    ...one iteration...
    record_iteration(monitor, time.perf_counter() - t0)
    delta = float(fit) - float(fit_prev)
    if verbose: print(...)

with three subtly different verbose formats and two dtype-inconsistent
delta computations (``float(fit - fit_prev)`` subtracts on device in the
factor dtype while the tol check compared host floats).
:class:`IterationRecorder` is that block, once: an ``"iteration"`` span
(when tracing), the StragglerMonitor feed *plus* its escalation check
(so single-host runs see slow-iteration flags through the metrics
registry too), the fit-trajectory metrics, and the one canonical
verbose line every method now prints::

      its = 3  fit = 0.812345  delta = +1.234e-02
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Iterator, Optional

from repro.obs import trace as obs_trace
from repro.obs.metrics import get_registry
from repro.obs.recorder import record_event


def record_iteration(monitor, dt: float) -> None:
    """Feed one iteration's wall time to a StragglerMonitor (if any)."""
    if monitor is not None:
        from repro.dist.straggler import record_step_times

        record_step_times(monitor, dt)


class IterationRecorder:
    """Per-driver-call recorder for the iteration loop.

    ``iteration(it)`` is the context manager wrapping one iteration's
    work; ``progress(it, fit, fit_prev)`` computes the dtype-consistent
    delta, records the iteration's wall time, prints the shared verbose
    line, and returns the delta for the fit loop's tol check.  With
    observability disabled (no active tracer) the per-iteration cost is one
    perf_counter pair and an ``is None`` check — no tracer or registry
    traffic at all.
    """

    __slots__ = ("method", "monitor", "verbose", "_observed", "_t0")

    def __init__(self, method: str, *, monitor=None,
                 verbose: bool = False) -> None:
        self.method = method
        self.monitor = monitor
        self.verbose = verbose
        self._observed = obs_trace.tracing()

    @contextmanager
    def iteration(self, it: int) -> Iterator[None]:
        self._t0 = time.perf_counter()
        with obs_trace.span("iteration", method=self.method, i=int(it)):
            yield

    def progress(self, it: int, fit, fit_prev) -> float:
        """One dtype-consistent delta scalar: cast both fits to python
        float FIRST, then subtract — printing ``float(fit - fit_prev)``
        (a bf16/f32 device subtraction) while comparing
        ``abs(float(fit) - float(fit_prev))`` against tol let the
        printed delta disagree with the stop decision.

        Reading the fit to the host waits for the iteration's device work,
        so the wall time recorded here runs from :meth:`iteration`'s entry
        to that read: the iteration's time on the device, not only its
        dispatch."""
        delta = float(fit) - float(fit_prev)
        dt = time.perf_counter() - self._t0
        record_iteration(self.monitor, dt)
        if self.monitor is not None:
            # escalations land in the metrics registry inside check() —
            # visible on single hosts, not just under the dist launcher
            self.monitor.check()
        if self._observed:
            registry = get_registry()
            registry.counter("fit.iterations").inc()
            registry.histogram("fit.iteration_ms").observe(dt * 1e3)
            record_event("iteration", method=self.method, i=int(it),
                         ms=dt * 1e3)
        if self.verbose:
            print(f"  its = {it + 1}  fit = {float(fit):.6f}  "
                  f"delta = {delta:+.3e}")
        if self._observed:
            get_registry().gauge("fit.fit").set(float(fit))
        return delta
