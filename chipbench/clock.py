"""Host-side timing helpers: compile seconds, spans, device facts."""
from __future__ import annotations

import sys

import jax

# the events JAX reports for each program it traces, lowers and compiles
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling while active, and
    how many programs went to the backend compiler (or its cache)."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self)


def span(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    return jax.profiler.TraceAnnotation(name)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """The peak on the fullest device: the larger of the allocator's
    in-use and reserved peaks (a program's temporaries show only in the
    reserved one)."""
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)),
                   int(stats.get("peak_bytes_reserved", 0)))
    return peak
