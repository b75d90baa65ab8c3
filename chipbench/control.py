"""Readings that set the limits of ``correct``: the program's on many
seeds and the control's on a few, at a cell's own size.  The benchmark's
runs do not run this.

    python3 chipbench/control.py --workload yelp.fit --seeds 1-12 \
        --control-seeds 101-103

For a fit cell each seed sets the cell up, runs a ``--seconds`` window of
sweeps and checks its first sweep, as a run does; the control takes the
program's place for that sweep (``reference.control_sweep``).  For a serving cell each seed drives a
``--seconds`` window at the cell's own rate and checks its answers; the
control answers the same requests (``reference.control_top_k`` and
``control_values_at``).  Prints one JSON line per seed, then the largest
program reading and the smallest control reading of each number.
"""
from __future__ import annotations

import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def fit_reading(cell, seed: int, control: bool, seconds: float) -> dict:
    import jax

    from chipbench import fit_cell, reference

    state = fit_cell.setup(cell.config, cell.traffic, seed, {})
    before = jax.device_get(fit_cell.copy_factors(state["factors"]))
    if control:
        t = state["tensor"].program
        factors, lam, fit = reference.control_sweep(t.inds, t.vals,
                                                    state["factors"])
        after = {"factors": factors, "lmbda": lam, "fit": float(fit)}
    else:
        _, after, _, _ = fit_cell.window(state, seconds,
                                         float(cell.traffic["ahead_s"]))
    return fit_cell.check_sweep(state["tensor"], before, after)


def serve_reading(cell, seed: int, control: bool, seconds: float,
                  served=None) -> dict:
    """``served``: the ``(server, models, dims)`` of ``serve_cell.setup``,
    shared by the readings of one process (the tenants' models are fixed
    by the configuration); set up and closed here when not given."""
    import jax
    import numpy as np

    from chipbench import reference, serve_cell, traffic

    mix = cell.traffic
    server, models, dims = served or serve_cell.setup(cell.config, mix)
    sched = traffic.open_loop(mix, dims, seed, seconds)
    try:
        if not control:
            out = serve_cell.drive(server, sched, mix, mix["grace_s"])
            return serve_cell.check(models, sched, out["futures"], mix, seed)
    finally:
        if served is None:
            server.close()
    answers = [None] * len(sched)
    for i in serve_cell.sample(sched, 1, mix["check_sample"], seed):
        factors, lam = models[sched.tenant[i]]
        answers[i] = np.asarray(reference.control_values_at(
            factors, lam, sched.coords[i]))
    idx = serve_cell.sample(sched, 0, mix["check_sample"], seed)
    for t, (factors, lam) in enumerate(models):
        rows = idx[sched.tenant[idx] == t]
        if rows.shape[0]:
            scores, items = jax.device_get(reference.control_top_k(
                factors, lam, sched.users[rows], int(mix["k"])))
            for j, i in enumerate(rows):
                answers[i] = (scores[j], items[j])
    return serve_cell.check(models, sched, answers, mix, seed)


def seeds(text: str) -> list:
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="window before each reading")
    args = ap.parse_args(argv)

    from chipbench import spec
    from chipbench.clock import device_info
    from chipbench.run import enable_cache

    cell = spec.resolve(args.workload)
    print(json.dumps({"device": device_info(), "cache": enable_cache()}),
          flush=True)
    kind = cell.traffic["kind"]
    names = list(cell.config["limits"][kind])
    rows = {False: [], True: []}
    served = None
    if kind == "open_loop":
        from chipbench import serve_cell

        served = serve_cell.setup(cell.config, cell.traffic)
    for control, group in ((False, args.seeds), (True, args.control_seeds)):
        for seed in group:
            if kind == "sweeps":
                nums = fit_reading(cell, seed, control, args.seconds)
            else:
                nums = serve_reading(cell, seed, control, args.seconds,
                                     served)
            row = {"seed": seed, "control": control,
                   **{k: float(v) for k, v in nums.items()
                      if isinstance(v, (int, float))}}
            rows[control].append(row)
            print(json.dumps(row), flush=True)
            gc.collect()
    summary = {}
    for k in names:
        summary[k] = {
            "program_max": max((r[k] for r in rows[False]), default=None),
            "control_min": min((r[k] for r in rows[True]), default=None)}
    print(json.dumps({"summary": summary}), flush=True)
    if served is not None:
        served[0].close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
