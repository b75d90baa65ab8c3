"""Find the highest offered rate a serving cell's server sustains: one sweep
of offered rates in one process, on the cell's own set-up and mix.

    python3 chipbench/knee.py --workload yelp.serve --seed 5 \
        --rates 200,500,1000,2000 --seconds 5

A rate is sustained where at least 99% of what was offered completes within
the window (and a quarter second after its last request was due) and the
queue does not grow across it: the median latency of the
last fifth of the requests stays under twice that of the first fifth.  One
JSON line per rate.  The benchmark's runs do not run this; the cell's rate
is fixed in its traffic file at about four fifths of the knee.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main(argv=None) -> int:
    import argparse

    import numpy as np

    from chipbench import serve_cell, spec, traffic
    from chipbench.clock import device_info
    from chipbench.run import enable_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--grace", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    print(json.dumps({"device": device_info(), "cache": enable_cache()}),
          flush=True)
    mix = cell.traffic
    server, _, dims = serve_cell.setup(cell.config, mix)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            sched = traffic.open_loop(mix, dims, args.seed, args.seconds,
                                      rate=rate)
            out = serve_cell.drive(server, sched, mix, args.grace)
            done = out["done"]
            in_window = np.nan_to_num(done, nan=np.inf) <= out["close"] + 0.25
            lat = (done - out["due"]) * 1e3
            fifth = max(1, len(sched) // 5)
            first = float(np.nanmedian(lat[:fifth]))
            last = float(np.nanmedian(lat[-fifth:]))
            share = float(in_window.mean())
            row = {"rate_per_s": rate, "offered": len(sched),
                   "completed_in_window": share,
                   "p50_ms": float(np.nanmedian(lat)),
                   "p99_ms": serve_cell.p99(np.nan_to_num(lat, nan=np.inf)),
                   "first_fifth_p50_ms": first, "last_fifth_p50_ms": last,
                   "late_p99_ms": serve_cell.p99(
                       (out["sent"] - out["due"]) * 1e3),
                   "sustained": bool(share >= 0.99 and last < 2 * first)}
            print(json.dumps(row), flush=True)
            # let a backlog drain before the next rate
            for fut in out["futures"]:
                fut.exception(timeout=120)
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
