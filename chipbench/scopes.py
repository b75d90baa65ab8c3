"""Device time of the fit window by program scope.

The program names its device work by layer with ``jax.named_scope``:
``mttkrp/mode{n}`` (with ``gather`` and ``kernel`` inside it) and
``epilogue/mode{n}``.  A scope reaches each compiled op as the path of its
``op_name``.  A TPU trace names each op (``%fusion.4 = f32[...] fusion(...)``)
and each run of a program (``jit__iteration_impl(<fingerprint>)`` on the
``XLA Modules`` line), but not the op's path: :class:`HloPaths` maps the one
to the other from the compiled text of the program the window runs, and
:func:`reduce_scopes` sums each scope's device time inside the harness's
``window`` span (:func:`scope_of` reads a path).

``scope_report.py`` prints that reduction for a cell's traced window;
``record_sweep_trace.py`` records the chip trace the tests reduce.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Optional

from . import trace

# the program's layers and the MTTKRP's parts, as its named scopes put them
# on each op's ``op_name`` path
LAYERS = ("mttkrp", "epilogue")
PARTS = ("gather", "kernel")
_MODE = re.compile(r"^mode(\d+)$")
_MODULES_LINE = "XLA Modules"


def scope_of(op_name: str) -> Optional[str]:
    """The program scope an op's ``op_name`` path lies in:
    ``mttkrp/mode{n}/gather``, ``mttkrp/mode{n}/kernel``, ``mttkrp/mode{n}``
    (the MTTKRP's index and output ops), ``epilogue/mode{n}``, or None.

    Read by path component, not by prefix: the kernels' own ``jax.jit``
    nests as ``jit(f)/mttkrp/mode0/jit(mttkrp)/gather/...``.  The last
    component names the op itself, never a scope."""
    parts = op_name.split("/")[:-1]
    for i in range(len(parts) - 1):
        mode = _MODE.match(parts[i + 1])
        if parts[i] in LAYERS and mode:
            scope = f"{parts[i]}/mode{mode.group(1)}"
            if parts[i] == "mttkrp":
                part = next((p for p in parts[i + 2:] if p in PARTS), None)
                if part is not None:
                    scope += "/" + part
            return scope
    return None


_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*op_name="([^"]*)"')


@dataclasses.dataclass
class HloPaths:
    """Each op of one compiled program and its ``op_name`` path.  A fusion
    carries the path of its root op."""

    module: str
    paths: dict                         # op name -> op_name path

    @classmethod
    def from_text(cls, text: str) -> "HloPaths":
        """From a compiled program's text (``Compiled.as_text()``)."""
        head = _HLO_MODULE.match(text)
        return cls(module=head.group(1) if head else "",
                   paths=dict(m.groups() for m in map(
                       _HLO_OP.match, text.splitlines()) if m))

    def path(self, module: Optional[str], name: str) -> Optional[str]:
        """The path of the op a trace event names, in a run of ``module``;
        None for an op of another program."""
        if module != self.module:
            return None
        return self.paths.get(name.lstrip("%").split(" ")[0])


def window_program(state: dict) -> str:
    """The compiled text of the program a fit cell's window runs
    (``chipbench.fit_cell.dispatch``), loaded from the compile cache where
    the warm-up put it."""
    from repro.core import cpals

    return cpals._iteration_jit(cpals.donate_buffers()).lower(
        state["ws"], state["factors"], state["grams"], state["norm_x_sq"],
        impls=state["impls"], norm_kind="2", with_fit=True).compile(
        ).as_text()


@dataclasses.dataclass
class Scoped:
    """A traced window's device time by program scope; seconds, averaged
    over the devices."""

    window_s: float
    busy_s: float                       # every op in the window
    scopes: dict                        # program scope -> seconds
    scoped_s: float                     # busy under some program scope

    @property
    def unscoped_pct(self) -> float:
        """Share of the window's busy time that no program scope claims."""
        return 100.0 * (1.0 - self.scoped_s / self.busy_s)

    def scope_s(self, layer: str, part: Optional[str] = None
                ) -> Optional[float]:
        """Device seconds under ``layer``'s scope of every mode, or only
        under its ``part``; None where no op carries it."""
        hits = [v for k, v in self.scopes.items()
                if k.split("/")[0] == layer
                and (part is None or k.split("/")[2:] == [part])]
        return sum(hits) if hits else None


def _module_at(modules: list, t: float) -> Optional[str]:
    """The program whose run, of ``modules`` (``(start_ns, name)``,
    sorted), an op starting at ``t`` belongs to: the last one started."""
    i = bisect.bisect_right(modules, (t, "\uffff"))
    return modules[i - 1][1] if i else None


def reduce_scopes(data, hlo: HloPaths, *,
                  window: str = trace.WINDOW) -> Scoped:
    """Each program scope's device seconds inside the ``window`` span of a
    ``jax.profiler.ProfileData``, with ``hlo`` the window program's op
    paths.  Window and busy time are :func:`trace.reduce_profile`'s."""
    red = trace.reduce_profile(data, window=window)
    w0 = min(s for s, _ in red.spans[window])
    w1 = max(e for _, e in red.spans[window])
    per_scope: dict = defaultdict(float)
    claimed = []
    for plane in data.planes:
        if not trace._DEVICE_PLANE.match(plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == trace._OPS_LINE:
                ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events)
            elif line.name == _MODULES_LINE:
                modules.extend((ev.start_ns, ev.name.split("(")[0])
                               for ev in line.events)
        modules.sort()
        mine = []
        for s, e, name in ops:
            path = hlo.path(_module_at(modules, s), name)
            scope = scope_of(path) if path else None
            if scope is not None and min(e, w1) > max(s, w0):
                per_scope[scope] += (min(e, w1) - max(s, w0)) / 1e9
                mine.append((s, e))
        claimed.append(trace._merge(mine))
    n = red.devices
    scoped_s = dataclasses.replace(red, intervals=claimed).busy_in(w0, w1)
    return Scoped(window_s=red.window_s, busy_s=red.busy_s,
                  scopes={k: v / n for k, v in sorted(per_scope.items())},
                  scoped_s=scoped_s)


def reduce_scopes_file(path: str, hlo: HloPaths, **kw) -> Scoped:
    """:func:`reduce_scopes` of the trace at ``path`` (an ``.xplane.pb``)."""
    from jax.profiler import ProfileData

    return reduce_scopes(ProfileData.from_file(path), hlo, **kw)
