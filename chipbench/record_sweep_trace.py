"""Record the small TPU trace of the program's own sweep that
``test_chipbench_scopes.py`` reduces.

    python3 chipbench/record_sweep_trace.py OUT_DIR

Runs on a TPU.  The program's own fused sweep (``repro.core.cpals.
_iteration``, plan ``pallas`` on every mode) at a small size: two warm-up
sweeps, then a ``window`` span holding two ``sweep`` spans, each one sweep
waited on, with a short sleep between them; after the window one ``probe``
span per mode, each a jitted call of the public ``mttkrp``, as the fit
cells' traced runs make them.  A sleep of ``MARGIN_S`` opens the window,
and another parts it from the probes: on a v5e the trace puts the
device's ops about a millisecond earlier than the host spans that
dispatch them.  Writes beside the trace the lines of the window program's
compiled text that the reduction reads (its header and every op with an
``op_name``: ``sweep_small.hlo.txt``), prints the planes, lines and first
events of the ``.xplane.pb`` and its reduction by program scope.  Copy
both files to ``chipbench/testdata/``, the trace as
``sweep_small.xplane.pb``.

At this size a sweep takes a few milliseconds, so that the few
microseconds of ops under no program scope (the layout copies of the
factors the program is handed) stay a small share of it, as at the cells'
sizes.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

DIMS = (600, 400, 800)
NNZ = 400_000
RANK = 35
SEED = 7
MARGIN_S = 0.02


def setup() -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench import data
    from repro.core.cpals import build_workspace, init_factors
    from repro.core.gram import gram
    from repro.plan import plan_decomposition

    t = data.make_tensor(DIMS, NNZ, 0.0, 0, SEED).program
    plan = plan_decomposition(t, "pallas", rank=RANK, with_stats=False)
    ws = jax.block_until_ready(build_workspace(t, plan))
    factors = init_factors(t.dims, RANK, jax.random.PRNGKey(SEED))
    return {"ws": ws, "impls": plan.impls, "plan": plan, "factors": factors,
            "grams": tuple(gram(a) for a in factors),
            "norm_x_sq": jnp.sum(t.vals ** 2)}


def main(out: str) -> int:
    import jax
    from jax.profiler import ProfileData, ProfileOptions

    from chipbench import fit_cell, scopes, trace

    state = setup()
    for it in range(2):
        state = fit_cell.sweep(state, "max" if it == 0 else "2")
    probes = fit_cell.probe_fns(state)
    for ws, fn in probes:
        jax.block_until_ready(fn(ws, state["factors"]))
    Path(out).mkdir(parents=True, exist_ok=True)
    text = "\n".join(line for line in scopes.window_program(state)
                     .splitlines()
                     if line.startswith("HloModule") or 'op_name="' in line)
    (Path(out) / "sweep_small.hlo.txt").write_text(text + "\n")

    options = ProfileOptions()
    # the file stays small; the ops' paths come from the text beside it
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out, profiler_options=options)
    with jax.profiler.TraceAnnotation("window"):
        time.sleep(MARGIN_S)
        for i in range(2):
            with jax.profiler.TraceAnnotation("sweep"):
                state = fit_cell.sweep(state)
            if i == 0:
                time.sleep(0.002)
    time.sleep(MARGIN_S)
    for ws, fn in probes:
        with jax.profiler.TraceAnnotation("probe"):
            jax.block_until_ready(fn(ws, state["factors"]))
    jax.profiler.stop_trace()

    path = trace.find_xplane(out)
    print("XPLANE", path, Path(path).stat().st_size)
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            for ev in events[:4]:
                print("     ", repr(ev.name), ev.start_ns, ev.duration_ns)
    red = trace.reduce_trace(path)
    print("REDUCED", red.window_s, red.busy_s, red.idle_pct, red.ops,
          red.gaps, red.span_busy("sweep"), red.span_busy("probe"))
    sc = scopes.reduce_scopes_file(path, scopes.HloPaths.from_text(text))
    print("SCOPES", sc.scoped_s, sc.unscoped_pct, sc.scopes)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
