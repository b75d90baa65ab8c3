"""Device time of a fit cell's window by program scope.

    python3 chipbench/scope_report.py --workload yelp.fit --seed 7 \
        --seconds 10

Runs on a TPU.  Sets a cell of traffic kind ``sweeps`` up as ``run.py``
does (``fit_cell.setup``), takes the compiled text of the program its
window runs, then runs the window twice from where set-up left it: once
with the profiler off and once under it.  Prints one JSON line last on
stdout: each window's sweeps and ``sweep_ms`` (so the cost of tracing),
the traced window's busy time, the share of it that no program scope
claims, and the device milliseconds per sweep of every scope and of the
sums ``mttkrp_gather_ms``, ``mttkrp_kernel_ms`` and ``epilogue_ms``
(``scopes.py``).  A program without the scopes reads no scope, and its
sums are null.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def per_sweep_ms(seconds, sweeps: int):
    return None if seconds is None else 1e3 * seconds / sweeps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from chipbench import fit_cell, run, scopes, spec, trace
    from chipbench.clock import device_info, log

    cell = spec.resolve(args.workload, ROOT)
    if cell.traffic["kind"] != "sweeps":
        raise SystemExit(f"{args.workload} runs no ALS sweeps")
    dev = device_info()
    if dev["platform"] != "tpu":
        log(f"scope_report: needs a TPU; JAX found {dev}")
        return 1
    log(f"device: {dev}; compile cache {run.enable_cache()}")
    state = fit_cell.setup(cell.config, cell.traffic, args.seed, {})
    hlo = scopes.HloPaths.from_text(scopes.window_program(state))
    ahead_s = float(cell.traffic["ahead_s"])

    state, _, sweeps, window_s = fit_cell.window(state, args.seconds,
                                                 ahead_s)
    out = {"workload": args.workload, "seed": args.seed,
           "untraced": {"sweeps": sweeps,
                        "sweep_ms": 1e3 * window_s / sweeps}}

    trace_dir = tempfile.mkdtemp(prefix="chipbench-scopes-")
    jax.profiler.start_trace(trace_dir)
    state, _, sweeps, window_s = fit_cell.window(state, args.seconds,
                                                 ahead_s)
    jax.profiler.stop_trace()
    sc = scopes.reduce_scopes_file(trace.find_xplane(trace_dir), hlo)
    shutil.rmtree(trace_dir, ignore_errors=True)
    out["traced"] = {
        "sweeps": sweeps, "sweep_ms": 1e3 * window_s / sweeps,
        "window_s": sc.window_s, "busy_s": sc.busy_s,
        "unscoped_pct": sc.unscoped_pct,
        "mttkrp_gather_ms": per_sweep_ms(sc.scope_s("mttkrp", "gather"),
                                         sweeps),
        "mttkrp_kernel_ms": per_sweep_ms(sc.scope_s("mttkrp", "kernel"),
                                         sweeps),
        "epilogue_ms": per_sweep_ms(sc.scope_s("epilogue"), sweeps),
        "scopes_ms": {k: per_sweep_ms(v, sweeps)
                      for k, v in sc.scopes.items()}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
