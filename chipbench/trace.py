"""From a profiler trace (``.xplane.pb``) to device busy time, per-op time
and idle gaps attributed to the harness's own host spans.

The harness marks what the host is doing with ``jax.profiler.
TraceAnnotation`` spans named in :data:`SPANS`; they land on the host plane
of the same trace, on the same clock as the device's ops.  Busy time is the
union of the intervals in which an op runs on a device, clipped to the
``window`` span and averaged over the devices; an idle gap is an interval of
the window in which no op runs, named after the innermost harness span that
holds its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Optional

# host spans the harness opens; set-up ones land in a trace only when a
# trace covers set-up
SPANS = ("generate", "ingest", "sort", "warmup", "window", "sweep",
         "submit", "execute-wait", "check", "probe")
WINDOW = "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class Reduced:
    """What one traced window reduces to; times in seconds."""

    window_s: float
    busy_s: float                       # mean over devices
    devices: int
    ops: list                           # [[op name, seconds]], most first
    gaps: list                          # [[host span, seconds]], longest first
    spans: dict                         # span name -> [(start_ns, end_ns)]
    intervals: list                     # per device: merged (start, end) ns
                                        # of every op in the trace

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def busy_in(self, start_ns: float, end_ns: float) -> float:
        """Device-busy seconds inside ``[start_ns, end_ns)``, averaged over
        the devices."""
        total = 0.0
        for merged in self.intervals:
            total += sum(max(0.0, min(e, end_ns) - max(s, start_ns))
                         for s, e in merged)
        return total / len(self.intervals) / 1e9

    def span_busy(self, name: str) -> list:
        """Device-busy seconds inside each host span called ``name``."""
        return [self.busy_in(s, e) for s, e in self.spans.get(name, [])]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _merge(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_trace(path: str, **kw) -> Reduced:
    """Reduce the trace at ``path`` (an ``.xplane.pb``)."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), **kw)


def reduce_profile(data, *, window: str = WINDOW,
                   span_names=SPANS) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` over the harness's ``window``
    span.  Raises where the trace holds no window span or no device op in
    it: a device metric is never read from nothing."""
    spans: dict = defaultdict(list)
    devices: list = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in span_names:
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        elif _DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events)
            devices.append(ops)
    if not spans.get(window):
        raise ValueError(f"no host span {window!r} in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    w0 = min(s for s, _ in spans[window])
    w1 = max(e for _, e in spans[window])

    per_op: dict = defaultdict(float)
    for ops in devices:
        for s, e, name in ops:
            if min(e, w1) > max(s, w0):
                per_op[name] += (min(e, w1) - max(s, w0)) / 1e9
    merged_all = [_merge([(s, e) for s, e, _ in ops]) for ops in devices]
    n = len(devices)
    red = Reduced(window_s=(w1 - w0) / 1e9, busy_s=0.0, devices=n, ops=[],
                  gaps=[], spans=dict(spans), intervals=merged_all)
    red.busy_s = red.busy_in(w0, w1)
    if red.busy_s <= 0.0:
        raise ValueError(f"no device op inside the {window!r} span")

    gaps = []
    for merged in merged_all:
        prev = w0
        for s, e in [iv for iv in merged if iv[1] > w0 and iv[0] < w1] \
                + [(w1, w1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    named = [[_holder(spans, (g0 + g1) / 2, window), (g1 - g0) / 1e9]
             for g0, g1 in gaps]
    named.sort(key=lambda g: -g[1])
    red.gaps = named[:TOP]
    red.ops = sorted(([k, v / n] for k, v in per_op.items()),
                     key=lambda o: -o[1])[:TOP]
    return red


def _holder(spans: dict, t: float, window: str) -> str:
    """The innermost harness span holding instant ``t``; the window itself
    when no other span does."""
    best: Optional[tuple] = None
    for name, ivs in spans.items():
        if name == window:
            continue
        for s, e in ivs:
            if s <= t < e and (best is None or e - s < best[0]):
                best = (e - s, name)
    return best[1] if best else window
