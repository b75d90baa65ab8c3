"""The main path on a TPU, at yelp's published scale, checked.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the dist executor on a 2x2 mesh

One chip: ingest -> plan("auto") -> CP-ALS fit -> two-tenant serving of the
synthetic yelp tensor (8.0M non-zeros, dims 41000 x 11000 x 75000, skew 1.5;
``repro.core.PAPER_DATASETS``) at the paper's rank 35, all through
``repro.api.Session`` in this one process.  It checks that each mode the
plan puts on a Pallas kernel compiled to one (``tpu_custom_call``), that each
mode's MTTKRP agrees with a float64 evaluation on the host and with the
plain-jnp ``segment`` path run at ``Precision.HIGHEST``, that the final fit
agrees with a ``segment`` fit from the same init, and that every served
answer agrees with a float64 numpy evaluation of the factors.

``--four-chips`` runs only the dist executor (``dist_cp_als`` on a
("data", "model") = 2x2 mesh) and the one-chip fit it is compared with.

The tensor is generated from ``--seed`` and every run starts cold: no ingest
cache, no autotune store.  Numbers also go to
``artifacts/chip_smoke/*.json``.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

OUT_DIR = ROOT / "artifacts" / "chip_smoke"

DATASET = "yelp"
SCALE = 1.0
RANK = 35
SWEEPS = 4   # sweeps 1 and 2 compile; 3 and 4 are steady
TENANTS = ("alpha", "beta")

# Tolerances.  A bfloat16 path rounds each operand to 8 mantissa bits and
# fails each: rounding yelp's MTTKRP products to bfloat16 moves every mode
# 1.1e-3 to 1.4e-3 (relative Frobenius) off float64.
MTTKRP_RTOL = 1e-5   # per mode, ||got - f64||_F / ||f64||_F
# per mode against the segment path, whose own float32 scatter-add drifts
# up to 1.0e-4 from float64 on yelp's hot rows (long sequential sums)
SEGMENT_RTOL = 3e-4
FIT_ATOL = 1e-5      # |fit - reference fit|
SERVE_RTOL = 1e-5    # served values and scores vs float64, relative to
                     # the largest sum of absolute rank-1 terms in the batch

# the events JAX reports for each program it traces, lowers and compiles
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling while active."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def __enter__(self) -> "CompileClock":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)


def timed(fn):
    """``(fn(), seconds)``, the clock stopped once the result is ready."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def rel_frob(got, ref) -> float:
    got = np.asarray(got, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_config(seed: int, sweeps: int, scale: float):
    from repro.api import (DataConfig, ExecConfig, MethodConfig, PlanConfig,
                           RunConfig, ServeConfig)

    return RunConfig(
        data=DataConfig(dataset=DATASET, scale=scale, seed=seed),
        plan=PlanConfig(policy="auto"),
        method=MethodConfig(name="cp_als", rank=RANK, niters=sweeps,
                            seed=seed),
        # the monitor receives each sweep's wall time
        exec=ExecConfig(executor="local", monitor=True,
                        monitor_window=sweeps),
        serve=ServeConfig(tenants=TENANTS))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def setup(sess) -> dict:
    """Generate, ingest, plan and sort: everything before the first sweep."""
    tensor, gen_s = timed(sess.load_tensor)
    ing, ingest_s = timed(sess.ingest)
    plan, plan_s = timed(sess.plan)
    ws, sort_s = timed(lambda: ing.workspace(plan))
    log(f"setup: generate {gen_s:.3f}s  ingest {ingest_s:.3f}s  "
        f"plan {plan_s:.3f}s  sort {sort_s:.3f}s  "
        f"(nnz {tensor.nnz}, dims {tensor.dims})")
    log(sess.plan_report())
    return {"generate_s": gen_s, "ingest_s": ingest_s, "plan_s": plan_s,
            "sort_s": sort_s, "nnz": int(tensor.nnz),
            "dims": list(tensor.dims),
            "impls": list(plan.impls),
            "blocks": [int(w.num_blocks) for w in ws
                       if hasattr(w, "num_blocks")]}


def kernel_guard(sess) -> list[int]:
    """Lower each Pallas-planned mode's MTTKRP as the fit runs it and
    require the compiled kernel in it: a kernel that fell back to the
    Pallas interpreter lowers to plain XLA ops instead."""
    import jax

    from repro.core.mttkrp import get_impl, mttkrp

    ing, plan = sess.ingest(), sess.plan()
    ws = ing.workspace(plan)
    factors = tuple(jax.ShapeDtypeStruct((d, RANK), ing.tensor.vals.dtype)
                    for d in ing.dims)
    kernel_modes = []
    for p in plan.modes:
        if get_impl(p.impl).backend != "tpu":
            log(f"kernel guard: mode {p.mode} runs {p.impl}, no Pallas "
                f"kernel")
            continue
        hlo = jax.jit(partial(mttkrp, mode=p.mode, impl=p.impl)).lower(
            ws[p.mode], factors).as_text()
        check("tpu_custom_call" in hlo,
              f"mode {p.mode} ({p.impl}) lowered without tpu_custom_call")
        log(f"kernel guard: mode {p.mode} {p.impl} -> tpu_custom_call")
        kernel_modes.append(p.mode)
    return kernel_modes


def fit(sess) -> tuple[object, dict]:
    import jax

    with CompileClock() as clock:
        decomp, fit_s = timed(sess.fit)
    sweeps = list(sess.monitor().times())
    check(len(sweeps) == sess.cfg.method.niters,
          f"recorded {len(sweeps)} sweep times for "
          f"{sess.cfg.method.niters} sweeps")
    for i, s in enumerate(sweeps):
        # sweeps 1 and 2 each compile their program (max-norm first sweep,
        # 2-norm after): the compile seconds are reported apart below
        log(f"fit: sweep {i + 1}: {s:.4f}s")
    stats = jax.devices()[0].memory_stats() or {}
    out = {"fit": float(decomp.fit), "fit_s": fit_s,
           "compile_s": clock.seconds, "sweep_s": sweeps,
           "steady_sweep_s": (statistics.median(sweeps[2:])
                              if len(sweeps) > 2 else None),
           "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    log(f"fit: {out['fit']:.6f} after {len(sweeps)} sweeps in {fit_s:.3f}s; "
        f"compile {clock.seconds:.3f}s; steady sweep "
        f"{out['steady_sweep_s']}s; peak_bytes_in_use "
        f"{out['peak_bytes_in_use']}")
    log(f"fit: memory_stats {stats}")
    check(np.isfinite(out["fit"]), "the fit is not finite")
    return decomp, out


def mttkrp_f64(csf, factors) -> np.ndarray:
    """The mode's MTTKRP in float64 on the host, from the sorted workspace:
    the exact side of the parity check."""
    rows = np.asarray(csf.row_ids)
    ids = np.asarray(csf.other_ids)
    prod = np.asarray(csf.vals, dtype=np.float64)[:, None]
    for i, m in enumerate(csf.other_modes):
        prod = prod * np.asarray(factors[m], dtype=np.float64)[ids[:, i]]
    starts = np.flatnonzero(np.r_[True, rows[1:] != rows[:-1]])
    out = np.zeros((csf.num_rows, prod.shape[1]))
    out[rows[starts]] = np.add.reduceat(prod, starts, axis=0)
    return out


def parity(sess, decomp) -> dict:
    """Each mode's MTTKRP from the planned impl on the fit's own factors
    against float64 and against the plain-jnp segment path at HIGHEST
    precision; the whole fit against a segment fit from the same init."""
    import jax

    from repro.core.mttkrp import mttkrp
    from repro.methods import fit as methods_fit

    ing, plan = sess.ingest(), sess.plan()
    check(ing.relabeling is None, "parity compares in the ingest labels")
    ws = ing.workspace(plan)
    factors = tuple(decomp.factors)
    modes = []
    for p in plan.modes:
        got = jax.jit(partial(mttkrp, mode=p.mode, impl=p.impl))(
            ws[p.mode], factors)
        with jax.default_matmul_precision("highest"):
            seg = jax.jit(partial(mttkrp, mode=p.mode, impl="segment"))(
                ing.csf_for(p.mode), factors)
        exact = mttkrp_f64(ing.csf_for(p.mode), factors)
        row = {"mode": p.mode, "impl": p.impl,
               "vs_f64": rel_frob(got, exact),
               "vs_segment": rel_frob(got, seg),
               "segment_vs_f64": rel_frob(seg, exact)}
        log(f"parity: mode {p.mode} {p.impl}: rel Frobenius vs float64 "
            f"{row['vs_f64']:.3e} (tolerance {MTTKRP_RTOL:g}), vs segment "
            f"{row['vs_segment']:.3e} (tolerance {SEGMENT_RTOL:g}); segment "
            f"vs float64 {row['segment_vs_f64']:.3e}")
        modes.append(row)
        del got, seg, exact

    cfg = sess.cfg.method
    with jax.default_matmul_precision("highest"):
        ref_dec = methods_fit(ing, RANK, method="cp_als",
                              plan=ing.plan("segment", rank=RANK),
                              niters=cfg.niters, key=sess.method_key())
    fit_err = abs(float(decomp.fit) - float(ref_dec.fit))
    factor_errs = [rel_frob(a, b)
                   for a, b in zip(decomp.factors, ref_dec.factors)]
    # the factors are reported, not checked: a rank-35 model of a uniform
    # random tensor barely fits it, so its normal equations are badly
    # conditioned and rounding-level MTTKRP differences grow in the solves
    log(f"parity: fit {float(decomp.fit):.7f} vs reference "
        f"{float(ref_dec.fit):.7f}: |diff| {fit_err:.3e} (tolerance "
        f"{FIT_ATOL:g}); factor rel Frobenius (reported) "
        f"{[f'{e:.3e}' for e in factor_errs]}")
    for row in modes:
        check(row["vs_f64"] <= MTTKRP_RTOL,
              f"mode {row['mode']} MTTKRP is {row['vs_f64']:.3e} off float64")
        check(row["vs_segment"] <= SEGMENT_RTOL,
              f"mode {row['mode']} MTTKRP is {row['vs_segment']:.3e} off "
              f"the segment path")
    check(fit_err <= FIT_ATOL, f"fit differs by {fit_err:.3e}")
    return {"mttkrp": modes, "fit_abs_err": fit_err,
            "reference_fit": float(ref_dec.fit),
            "factor_rel_frob": factor_errs}


def serve(sess, decomp, *, seed: int, n_values: int, n_topk: int,
          batch: int = 32, k: int = 10) -> dict:
    """Closed-loop values_at and top_k requests against both tenants, each
    answer checked against float64 numpy on the fitted factors."""
    server = sess.decomp_server()
    dims = sess.serve_handle().dims
    lam = np.asarray(decomp.lmbda, dtype=np.float64)
    fac = [np.asarray(a, dtype=np.float64) for a in decomp.factors]
    weights = lam * fac[2].sum(axis=0)  # user mode 0, item mode 1
    rng = np.random.default_rng(seed)

    lat = {"values_at": [], "top_k": []}
    worst = {"values_at": 0.0, "top_k": 0.0}
    for i in range(n_values):
        coords = np.stack([rng.integers(0, d, batch) for d in dims],
                          axis=1).astype(np.int32)
        t0 = time.perf_counter()
        got = np.asarray(server.values_at(TENANTS[i % 2], coords))
        lat["values_at"].append(time.perf_counter() - t0)
        terms = (lam[None, :] * fac[0][coords[:, 0]] * fac[1][coords[:, 1]]
                 * fac[2][coords[:, 2]])
        want = terms.sum(axis=1)
        scale = max(np.abs(terms).sum(axis=1).max(), 1e-30)
        worst["values_at"] = max(worst["values_at"],
                                 float(np.abs(got - want).max() / scale))
    for i in range(n_topk):
        user = int(rng.integers(0, dims[0]))
        t0 = time.perf_counter()
        scores, items = server.top_k_for_user(TENANTS[i % 2], user, k=k)
        lat["top_k"].append(time.perf_counter() - t0)
        scores, items = np.asarray(scores), np.asarray(items)
        want = (fac[0][user] * weights) @ fac[1].T
        scale = max((np.abs(fac[0][user] * weights) @ np.abs(fac[1]).T).max(),
                    1e-30)
        kth_best = np.sort(want)[-k]
        check(len(set(items.tolist())) == k, "top_k repeated an item")
        err = max(float(np.abs(scores - want[items]).max() / scale),
                  # every returned item scores at least the true k-th best
                  float(max(0.0, kth_best - want[items].min()) / scale))
        worst["top_k"] = max(worst["top_k"], err)

    out = {}
    for kind, ts in lat.items():
        ms = np.asarray(ts) * 1e3
        out[kind] = {"requests": len(ts),
                     "p50_ms": float(np.percentile(ms, 50)),
                     "p99_ms": float(np.percentile(ms, 99)),
                     "max_rel_err": worst[kind]}
        log(f"serve: {kind} x{len(ts)} over {len(TENANTS)} tenants: "
            f"p50 {out[kind]['p50_ms']:.3f} ms  p99 "
            f"{out[kind]['p99_ms']:.3f} ms  max rel err "
            f"{worst[kind]:.3e} (tolerance {SERVE_RTOL:g})")
        check(worst[kind] <= SERVE_RTOL,
              f"served {kind} differs by {worst[kind]:.3e}")
    return out


def run_one_chip(*, seed: int, sweeps: int = SWEEPS, scale: float = SCALE,
                 n_values: int = 256, n_topk: int = 64) -> dict:
    from repro.api import Session

    with Session.from_config(run_config(seed, sweeps, scale)) as sess:
        result = {"setup": setup(sess)}
        result["kernel_modes"] = kernel_guard(sess)
        decomp, result["fit"] = fit(sess)
        result["parity"] = parity(sess, decomp)
        result["serve"] = serve(sess, decomp, seed=seed, n_values=n_values,
                                n_topk=n_topk)
    return result


def run_four_chips(*, seed: int, sweeps: int = SWEEPS,
                   scale: float = SCALE) -> dict:
    """``dist_cp_als`` through the Session on a 2x2 ("data", "model") mesh,
    against the one-chip fit of the same ingested tensor from the same
    init (the same method seed; the yelp dims divide the mesh, so
    ``dist_cp_als`` pads nothing and draws the same initial factors)."""
    import dataclasses

    import jax

    from repro.api import DataConfig, ExecConfig, Session

    cfg1 = run_config(seed, sweeps, scale)
    with Session.from_config(cfg1) as sess1:
        setup(sess1)
        dec1, fit1 = fit(sess1)
        cfg4 = dataclasses.replace(
            cfg1, data=DataConfig(),
            exec=ExecConfig(executor="dist",
                            mesh_shape={"data": 2, "model": 2}))
        before = [d.memory_stats() or {} for d in jax.devices()]
        # the dist session adopts the one-chip session's ingest as-is
        with Session.from_config(cfg4, tensor=sess1.ingest()) as sess4:
            log(sess4.plan_report())
            with CompileClock() as clock:
                dec4, dist_s = timed(sess4.fit)
    fit_err = abs(float(dec1.fit) - float(dec4.fit))
    factor_errs = [rel_frob(a, b) for a, b in zip(dec4.factors, dec1.factors)]
    log(f"four chips: dist fit {float(dec4.fit):.7f} vs one-chip "
        f"{float(dec1.fit):.7f}: |diff| {fit_err:.3e} (tolerance "
        f"{FIT_ATOL:g}); factor rel Frobenius (reported, see parity) "
        f"{[f'{e:.3e}' for e in factor_errs]}; dist fit {dist_s:.3f}s incl. "
        f"compile {clock.seconds:.3f}s")
    # devices 1-3 hold nothing before the dist fit: their peaks are their
    # shares of it; device 0's peak is the one-chip fit's
    memory = []
    for d, pre in zip(jax.devices(), before):
        stats = d.memory_stats() or {}
        row = {"id": d.id,
               "bytes_in_use_before_dist": pre.get("bytes_in_use"),
               "bytes_in_use": stats.get("bytes_in_use"),
               "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
        memory.append(row)
        log(f"four chips: device {d.id} memory_stats: bytes_in_use before "
            f"dist {row['bytes_in_use_before_dist']}  after "
            f"{row['bytes_in_use']}  peak_bytes_in_use "
            f"{row['peak_bytes_in_use']}")
    check(fit_err <= FIT_ATOL, f"dist fit differs by {fit_err:.3e}")
    return {"one_chip_fit": fit1, "dist_fit": float(dec4.fit),
            "dist_s": dist_s, "dist_compile_s": clock.seconds,
            "fit_abs_err": fit_err, "factor_rel_frob": factor_errs,
            "memory": memory}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated tensor and of the factors")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dist executor on a 2x2 mesh and the "
                         "one-chip fit it is compared with")
    args = ap.parse_args(argv)

    import jax

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev['platform']!r} ({dev['kind']}, {dev['count']} "
              f"device(s))", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if dev["count"] < want:
        print(f"chip_smoke: needs {want} TPU chips, found {dev['count']}",
              file=sys.stderr)
        return 1
    log(f"device: platform {dev['platform']}  kind {dev['kind']}  count "
        f"{dev['count']}  jax {jax.__version__}")

    from repro.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        result = run_four_chips(seed=args.seed)
    else:
        result = run_one_chip(seed=args.seed)
    result["device"] = dev
    result["jax"] = jax.__version__
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    name = "four_chips.json" if args.four_chips else "one_chip.json"
    (OUT_DIR / name).write_text(json.dumps(result, indent=1))
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
