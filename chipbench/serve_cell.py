"""Cells of traffic kind ``open_loop``: multi-tenant serving under Poisson
arrivals.

Set-up draws the tenants' models from the configuration's
``structure_seed`` (a deployment's models are fixed; ``--seed`` draws the
traffic), publishes them to a ``repro.serve.DecompServer`` with the
``ServeConfig`` defaults, and warms every (tenant, kind, bucket) program.
The program's jitted queries hold a tenant's factors as constants, so
models drawn per seed would compile anew in every run; fixed models let a
checkout's second run load every program from the compile cache.  In the window this process's main
thread is the load generator: it sends each request at its due time,
whether or not earlier ones have been answered, and the completion time of
each is taken where its future resolves.  A request's latency runs from
when it was due to be sent to its answer on the host, so a stall of the
server or of the generator counts against every request behind it.
"""
from __future__ import annotations

import shutil
import tempfile
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import data, reference, trace as trace_mod, traffic as traffic_mod
from .clock import CompileClock, log, memory_peak_bytes, span

KIND_METRIC = {"top_k": "top_k_p99_ms", "values_at": "values_at_p99_ms"}


def tenant_name(i: int) -> str:
    return f"tenant{i}"


def setup(cfg: dict, mix: dict):
    from repro.api import ServeConfig
    from repro.core.cpals import CPDecomp
    from repro.serve import DecompServer

    dims = tuple(int(d) for d in cfg["dims"])
    with span("generate"):
        models = jax.block_until_ready(
            data.make_models(dims, cfg["rank"], mix["tenants"],
                             cfg["structure_seed"]))
    server = DecompServer.from_config(ServeConfig())
    with span("warmup"):
        for i, (factors, lmbda) in enumerate(models):
            decomp = CPDecomp(factors=tuple(factors), lmbda=lmbda,
                              fit=jnp.float32(np.nan))
            model = server.publish(tenant_name(i), decomp, dims).model
            for b in model.buckets:
                model.values_at(np.zeros((b, len(dims)), np.int32))
                model.top_k(np.zeros((b,), np.int32), int(mix["k"]))
    return server, models, dims


def drive(server, sched, mix: dict, grace_s: float) -> dict:
    """Send ``sched`` open loop; returns due/sent/done times (seconds,
    ``perf_counter``) and the futures."""
    n = len(sched)
    done = np.full(n, np.nan)
    sent = np.empty(n)
    futures = [None] * n
    k = int(mix["k"])

    def finished(i, _fut):
        done[i] = time.perf_counter()

    base = time.perf_counter() + 0.01
    due = base + sched.due
    for i in range(n):
        lag = due[i] - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        tenant = tenant_name(int(sched.tenant[i]))
        with span("submit"):
            sent[i] = time.perf_counter()
            if sched.kind[i] == 0:
                fut = server.submit_top_k(tenant, sched.users[i:i + 1], k=k)
            else:
                fut = server.submit_values_at(tenant, sched.coords[i])
        fut.add_done_callback(partial(finished, i))
        futures[i] = fut
    close = base + sched.due[-1]
    with span("execute-wait"):
        for fut in futures:
            try:
                fut.exception(timeout=max(0.0, close + grace_s
                                          - time.perf_counter()))
            except TimeoutError:
                pass
    return {"due": due, "sent": sent, "done": done, "futures": futures,
            "base": base, "close": close}


def p99(values: np.ndarray) -> float:
    """Nearest-rank 99th percentile."""
    v = np.sort(values)
    return float(v[max(0, int(np.ceil(0.99 * v.shape[0])) - 1)])


def latencies(run: dict, sched, cap_s: float) -> dict:
    """Per kind: latency in ms of every request, a failed or missing one
    at the cap; and the number failed."""
    lat = (run["done"] - run["due"]) * 1e3
    failed = np.array([not f.done() or f.exception() is not None
                       for f in run["futures"]])
    lat = np.where(failed | np.isnan(lat), cap_s * 1e3, lat)
    out = {}
    for i, kind in enumerate(traffic_mod.KINDS):
        sel = sched.kind == i
        out[kind] = {"ms": lat[sel], "failed": int(failed[sel].sum())}
    return out


def run(cell, seed: int, seconds: float, traced: bool, t_start: float,
        limits: dict) -> dict:
    cfg, mix = cell.config, cell.traffic
    grace = float(mix["grace_s"])
    with CompileClock() as setup_clock:
        server, models, dims = setup(cfg, mix)
    sched = traffic_mod.open_loop(mix, dims, seed, seconds)
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f}s, {setup_clock.seconds:.3f}s of it compiling "
        f"({setup_clock.compiles} programs); {len(sched)} requests due at "
        f"{mix['rate_per_s']}/s")
    try:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if traced else None
        batches0 = server.queue.batches_executed
        if traced:
            jax.profiler.start_trace(trace_dir)
        with CompileClock() as window_clock, span("window"):
            out = drive(server, sched, mix, grace)
        if traced:
            jax.profiler.stop_trace()
        batches = server.queue.batches_executed - batches0
    finally:
        server.close()
    memory = memory_peak_bytes()
    reduced = None
    if traced:
        reduced = trace_mod.reduce_trace(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    lat = latencies(out, sched, seconds + grace)
    late = (out["sent"] - out["due"]) * 1e3
    e2e = {"setup_s": setup_s}
    failed = 0
    for kind, row in lat.items():
        e2e[KIND_METRIC[kind]] = p99(row["ms"])
        failed += row["failed"]
        log(f"window: {kind} x{row['ms'].shape[0]}: p50 "
            f"{np.median(row['ms']):.4f} ms  p99 {e2e[KIND_METRIC[kind]]:.4f}"
            f" ms  max {row['ms'].max():.4f} ms  failed {row['failed']}")
    completed = len(sched) - failed
    log(f"window: generator lateness p50 {np.median(late):.4f} ms  p99 "
        f"{p99(late):.4f} ms; {batches} batches, "
        f"{completed / max(batches, 1):.3f} requests per batch; "
        f"{window_clock.compiles} compiles inside the window")
    for i in range(mix["tenants"]):
        hist = _registry_histogram(f"serve.{tenant_name(i)}.query_ms")
        if hist:
            log(f"window: server-side query_ms {tenant_name(i)}: {hist}")

    with span("check"):
        nums = check(models, sched, out["futures"], mix, seed)
    ctx = {"kind": "open_loop", "trace": reduced, "completed": completed,
           "batches": batches, "compile_s": setup_clock.seconds}
    return {"e2e": e2e, "ctx": ctx, "memory": memory,
            "attempted": len(sched), "failed": failed, "reduced": reduced,
            "checks": {k: nums[k] for k in limits}}


def _registry_histogram(name: str):
    from repro.obs.metrics import get_registry

    return get_registry().snapshot().get(name)


def sample(sched, kind: int, size: int, seed: int) -> np.ndarray:
    idx = np.flatnonzero(sched.kind == kind)
    rng = np.random.default_rng([int(seed), 11, kind])
    return np.sort(rng.choice(idx, size=min(size, idx.shape[0]),
                              replace=False))


def check(models, sched, answers, mix: dict, seed: int) -> dict:
    """A seeded sample of each kind's answers against float64.  ``answers``
    holds, per request, its future or its ``(scores, items)`` / values."""
    host = [([np.asarray(a, np.float64) for a in f],
             np.asarray(lam, np.float64)) for f, lam in jax.device_get(models)]
    k = int(mix["k"])

    def result(i):
        a = answers[i]
        if hasattr(a, "result"):
            return a.result() if a.done() and a.exception() is None else None
        return a

    worst = {"values_at_err": 0.0, "top_k_err": 0.0}
    for i in sample(sched, 1, mix["check_sample"], seed):
        got = result(i)
        factors, lam = host[sched.tenant[i]]
        err = np.inf if got is None else reference.values_at_err(
            factors, lam, sched.coords[i], got)
        worst["values_at_err"] = max(worst["values_at_err"], err)
    idx = sample(sched, 0, mix["check_sample"], seed)
    for t in range(len(host)):
        rows = idx[sched.tenant[idx] == t]
        if rows.shape[0] == 0:
            continue
        got = [result(i) for i in rows]
        if any(g is None for g in got):
            worst["top_k_err"] = np.inf
            continue
        scores = np.concatenate([np.asarray(g[0]).reshape(1, -1)
                                 for g in got])
        items = np.concatenate([np.asarray(g[1]).reshape(1, -1)
                                for g in got])
        factors, lam = host[t]
        errs = reference.top_k_err(factors, lam, sched.users[rows], scores,
                                   items, k)
        worst["top_k_err"] = max(worst["top_k_err"], float(errs.max()))
    return worst
