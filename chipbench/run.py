"""Run one cell of ``BENCHMARK.json`` once, on the TPU this process finds.

    python3 chipbench/run.py --workload yelp.fit --seed 7 --seconds 10 \
        --trace 0

The process checks for a TPU (and for as many chips as the cell asks for)
and fails without one, keeps JAX's persistent compile cache at a fixed path
in the checkout, sets the cell up from ``--seed``, warms every shape the
window uses, measures for ``--seconds``, checks what the timed path produced
against the float64 reference, and prints one JSON line last on stdout:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics from a profiled run),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared, with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

# traffic kind -> the module that runs such a cell
RUNNERS = {"sweeps": "chipbench.fit_cell", "open_loop": "chipbench.serve_cell"}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache() -> str:
    import jax

    from repro.utils.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    # every program, however quick to compile, so that a second run of a
    # cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cell, *, seed: int, seconds: float, traced: bool,
             device: dict, t_start: float = T_START) -> dict:
    """Run ``cell`` and return the result line (as a dict)."""
    from chipbench.clock import log

    kind = cell.traffic["kind"]
    limits = cell.config["limits"][kind]
    runner = importlib.import_module(RUNNERS[kind])
    res = runner.run(cell, seed, seconds, traced, t_start, limits)
    res["ctx"]["device_kind"] = device["kind"]
    checks = {k: {"value": float(res["checks"][k]), "limit": float(v)}
              for k, v in limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values()) and res["failed"] == 0

    metrics = {}
    if traced:
        for m in cell.per_layer:
            value = cell.reader(m["name"])(res["ctx"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(res["e2e"][m["name"]]),
                                  "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=int(res["memory"]))
    line = {"correct": bool(correct), "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics}
    if traced:
        red = res["reduced"]
        dev.update(busy_s=red.busy_s, window_s=red.window_s)
        line["device"] = dev
        line["breakdown"] = {"device_ops": red.ops, "idle_gaps": red.gaps}
    else:
        line["device"] = dev
    line["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return line


def main(argv=None, *, root: Path = ROOT, require_chip: bool = True) -> int:
    args = parse(argv)
    from chipbench import spec

    try:
        cell = spec.resolve(args.workload, root)
    except spec.SpecError as exc:
        print(f"chipbench: {exc}", file=sys.stderr)
        return 2
    from chipbench.clock import device_info, log

    dev = device_info()
    if require_chip and (dev["platform"] != "tpu"
                         or dev["count"] < cell.chips):
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {dev['count']} {dev['platform']} device(s) "
              f"({dev['kind']})", file=sys.stderr)
        return 1
    log(f"device: {dev}; compile cache {enable_cache()}")
    line = run_cell(cell, seed=args.seed, seconds=args.seconds,
                    traced=bool(args.trace), device=dev)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
