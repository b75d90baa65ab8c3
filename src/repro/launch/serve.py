"""Serving launcher: the decomposition-serving path for the paper's own
CP-ALS workloads (plan-driven decompose, then batched reconstruction
queries).

  PYTHONPATH=src python -m repro.launch.serve --arch cpals-yelp --smoke \
      --batch 256 --queries 2048

It drives :class:`repro.api.Session`, shares its RunConfig with ``python -m
repro serve``, and the production serving layer on top of it is
``repro.serve`` (``python -m repro serve-daemon``).  CPU-sized with
--smoke; the production shapes are proven by the dry-run's cpals cells.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.core.coo import PAPER_WORKLOADS


def cpd_config(workload: str, *, smoke: bool, rank: int, niters: int,
               policy: str, seed: int, reorder: str, cache: str | None,
               method: str):
    """The launcher's declarative description: one RunConfig, shared with
    ``python -m repro serve`` and the dry-run planner."""
    from repro.api import (DataConfig, ExecConfig, MethodConfig, PlanConfig,
                           RunConfig, require_capability)

    # the one capability gate (raises with the registry listing if unknown)
    spec = require_capability(method, "local")
    return RunConfig(
        data=DataConfig(dataset=PAPER_WORKLOADS[workload],
                        scale=0.002 if smoke else 1.0, seed=seed,
                        reorder=reorder, cache=cache),
        plan=PlanConfig(policy=policy),
        method=MethodConfig(name=method, rank=rank, niters=niters, seed=seed),
        exec=ExecConfig(executor="local",
                        n_chunks=8 if spec.supports_streaming else None),
    )


def serve_cpd(workload: str, *, smoke: bool, batch: int, queries: int,
              rank: int = 16, niters: int = 10, policy: str = "auto",
              seed: int = 0, reorder: str = "identity",
              cache: str | None = None, method: str = "cp_als") -> dict:
    """Decompose a paper workload under a per-mode plan, then serve batched
    reconstruction queries (``values_at``) from the factor model.

    This is the decomposition-serving scenario: the decomposition is the
    compressed representation; a query is a coordinate batch and the answer
    is the reconstructed values.  ``--smoke`` scales the tensor to CPU size;
    the plan (and its report) is printed so the per-mode impl choice is
    visible at launch.

    Everything below is a thin wrapper over :class:`repro.api.Session` —
    ingest/plan/fit/serve_handle are the Session's cached stages, every
    method serves queries through the same ``values_at`` interface, and the
    same RunConfig drives ``python -m repro serve``.  ``--reorder`` /
    ``--cache`` are the ingest options; queries and factors stay in the
    tensor's ORIGINAL labels."""
    from repro.api import Session

    cfg = cpd_config(workload, smoke=smoke, rank=rank, niters=niters,
                     policy=policy, seed=seed, reorder=reorder, cache=cache,
                     method=method)
    sess = Session.from_config(cfg)
    # materialize the synthetic replica OUTSIDE the timed window so
    # ingest_s measures ingestion (and shows the cache win), not generation
    sess.load_tensor()
    t0 = time.time()
    ing = sess.ingest()
    t_ingest = time.time() - t0

    print(sess.plan_report())
    plan = sess.plan()
    plan_summary = plan.summary() if plan is not None \
        else "streaming:gather_scatter"
    t0 = time.time()
    dec = sess.fit()
    jax.block_until_ready(dec.fit)
    t_decomp = time.time() - t0

    # serve: batched coordinate -> reconstructed-value queries (the shared
    # ServeHandle benchmark loop — same numbers as `python -m repro serve`)
    bench = sess.serve_handle().benchmark(queries=queries, batch=batch,
                                          seed=seed)
    return {"fit": float(dec.fit), "decompose_s": t_decomp,
            "serve_s": bench["serve_s"], "plan": plan_summary,
            "method": method, "ingest_s": t_ingest,
            "cache_hit": ing.cache_hit, "qps": bench["qps"],
            "latency_ms": bench["latency_ms"]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=tuple(PAPER_WORKLOADS),
                    help="the paper workload to decompose and serve")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--queries", type=int, default=2048,
                    help="total reconstruction queries")
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--impl", default="auto",
                    help="planner policy (auto or impl name)")
    ap.add_argument("--method", default="cp_als",
                    help="decomposition method (repro.methods registry: "
                    "cp_als/cp_nn_hals/tucker_hooi/cp_als_streaming)")
    ap.add_argument("--reorder", default="identity",
                    help="ingest reordering "
                    "(identity/degree_sort/random_block)")
    ap.add_argument("--cache", default=None,
                    help="ingest cache root (warm relaunch skips "
                    "sort+stats)")
    args = ap.parse_args()
    out = serve_cpd(args.arch, smoke=args.smoke,
                    batch=args.batch, queries=args.queries,
                    rank=args.rank, niters=args.iters, policy=args.impl,
                    reorder=args.reorder, cache=args.cache,
                    method=args.method)
    print(f"[serve] method {out['method']}  plan {out['plan']}  "
          f"fit {out['fit']:.4f}  "
          f"ingest {out['ingest_s']:.2f}s"
          f"{' (cache hit)' if out['cache_hit'] else ''}  "
          f"decompose {out['decompose_s']:.2f}s  "
          f"serve {out['serve_s']:.2f}s ({out['qps']:,.0f} vals/s, "
          f"p50 {out['latency_ms']['p50']:.2f}ms "
          f"p99 {out['latency_ms']['p99']:.2f}ms)")


if __name__ == "__main__":
    main()
