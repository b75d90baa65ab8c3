"""Cells of traffic kind ``sweeps``: CP-ALS sweeps back to back.

Set-up generates the tensor (the configuration's fixed coordinates, values
from the seed), ingests and plans it through ``repro.api.Session``, builds
the sorted workspaces, initialises the factors from the seed's key as the
``cp_als`` driver does, and runs the warm-up sweeps: the first with the
max-norm program, the rest with the 2-norm one, so that both compiled
iteration programs are in place.  The window then runs 2-norm sweeps back
to back until ``--seconds`` have passed.  Each sweep is the program's fused
iteration (``repro.core.cpals._iteration``), called as the driver's loop in
``repro.methods.cp_als`` calls it; every sweep's fit is read to the host,
as that loop reads it, but the mix's ``ahead_s`` seconds of sweeps late,
so that a stall of the host does not leave the chip idle.  ``sweep_ms`` is
the window's wall time, up to the end of the last sweep sent, over the
sweeps sent.

The window's first sweep is what is checked: the state it started from
is kept on the host before the window opens, its result is copied on the
device before the next sweep consumes it, and once the window has closed
that sweep is held against the float64 reference (``reference.py``).  Its
place in the fit is fixed by the configuration, not by how many sweeps the
window completes: the model grows worse conditioned as the fit goes on,
and that alone moves the numbers compared.
"""
from __future__ import annotations

import collections
import shutil
import tempfile
import time
from functools import partial

import jax
import jax.numpy as jnp

from . import data, reference, trace as trace_mod
from .clock import CompileClock, log, memory_peak_bytes, span

PROBE_CALLS = 3


def session_config(cfg: dict, seed: int):
    from repro.api import ExecConfig, MethodConfig, PlanConfig, RunConfig

    return RunConfig(
        plan=PlanConfig(policy=cfg["plan"]),
        method=MethodConfig(name=cfg["method"], rank=int(cfg["rank"]),
                            seed=data.seed32(seed, 2)),
        exec=ExecConfig(executor=cfg["executor"]))


def dispatch(state: dict, norm_kind: str = "2") -> dict:
    """One fused ALS iteration from ``state``, dispatched: its fit stays a
    device array.  The factor and gram buffers are donated where the
    driver donates them: ``state``'s arrays are consumed."""
    from repro.core import cpals

    factors, grams, lmbda, fit = cpals._iteration(
        state["ws"], state["factors"], state["grams"], state["norm_x_sq"],
        impls=state["impls"], norm_kind=norm_kind, with_fit=True,
        donate=cpals.donate_buffers())
    return dict(state, factors=factors, grams=grams, lmbda=lmbda, fit=fit)


def sweep(state: dict, norm_kind: str = "2") -> dict:
    """One fused ALS iteration from ``state``, its fit read to the host."""
    state = dispatch(state, norm_kind)
    return dict(state, fit=float(state["fit"]))


def setup(cfg: dict, traffic: dict, seed: int, times: dict) -> dict:
    """Everything before the window; returns the cell's state after the
    warm-up sweeps."""
    from repro.api import Session
    from repro.core.cpals import init_factors
    from repro.core.gram import gram

    t0 = time.perf_counter()
    with span("generate"):
        tensor = data.make_tensor(cfg["dims"], cfg["nnz"], cfg["skew"],
                                  cfg["structure_seed"], seed)
    times["generate_s"] = time.perf_counter() - t0
    session = Session.from_config(session_config(cfg, seed),
                                  tensor=tensor.program)
    t0 = time.perf_counter()
    with span("ingest"):
        ing = session.ingest()
        plan = session.plan()
    times["ingest_s"] = time.perf_counter() - t0
    if ing.relabeling is not None:
        raise RuntimeError("the reference holds the generated labels; the "
                           "configuration must not relabel")
    t0 = time.perf_counter()
    with span("sort"):
        ws = jax.block_until_ready(ing.workspace(plan))
    times["csf_sort_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    with span("warmup"):
        t = ing.tensor
        factors = init_factors(t.dims, int(cfg["rank"]),
                               session.method_key(), dtype=t.vals.dtype)
        state = {"ws": ws, "impls": plan.impls, "factors": factors,
                 "grams": tuple(gram(a) for a in factors),
                 "norm_x_sq": jnp.sum(t.vals.astype(jnp.float32) ** 2)}
        for it in range(int(traffic["warmup_sweeps"])):
            state = sweep(state, "max" if it == 0 else "2")
    times["warmup_s"] = time.perf_counter() - t0
    log(f"setup: nnz {tensor.nnz}  dims {tensor.dims}  impls {plan.impls}  "
        + "  ".join(f"{k} {v:.3f}s" for k, v in times.items()))
    return dict(state, tensor=tensor, ing=ing, plan=plan)


def probe_fns(state: dict) -> list:
    """The program's public per-mode MTTKRP at the plan's impls, jitted by
    the harness: what ``mttkrp_ms`` times in the traced run."""
    from repro.core.mttkrp import mttkrp

    return [(state["ws"][p.mode],
             jax.jit(partial(mttkrp, mode=p.mode, impl=p.impl)))
            for p in state["plan"].modes]


def copy_factors(factors) -> tuple:
    """Device copies that outlive the donation of ``factors``."""
    return tuple(jnp.array(a, copy=True) for a in factors)


def window(state: dict, seconds: float, ahead_s: float):
    """Sweeps back to back for ``seconds``.  The first is waited for: it is
    the one checked, and its time sets how many sweeps make ``ahead_s``.
    After it the window keeps that many sweeps dispatched ahead of the one
    whose fit it reads, so that the chip stays fed while the host stands
    still.  When the time is up it sends nothing more, waits for every
    sweep sent, and reads the clock after that wait.  Returns the last
    state, the first sweep's result (its factors copied on the device
    before the next sweep consumes them), the sweeps completed and the
    window's wall time."""
    t0 = time.perf_counter()
    with span("window"):
        with span("sweep"):
            state = sweep(state)
        ahead = max(1, int(ahead_s / (time.perf_counter() - t0)))
        first = dict(state, factors=copy_factors(state["factors"]))
        sweeps = 1
        pending = collections.deque()
        while time.perf_counter() - t0 < seconds:
            with span("sweep"):
                state = dispatch(state)
            pending.append(state["fit"])
            sweeps += 1
            if len(pending) > ahead:
                with span("execute-wait"):
                    float(pending.popleft())
        with span("execute-wait"):
            jax.block_until_ready(list(pending))
        window_s = time.perf_counter() - t0
    return dict(state, fit=float(state["fit"])), first, sweeps, window_s


def run(cell, seed: int, seconds: float, traced: bool, t_start: float,
        limits: dict) -> dict:
    cfg = cell.config
    times: dict = {}
    with CompileClock() as setup_clock:
        state = setup(cfg, cell.traffic, seed, times)
        probes = []
        if traced:
            probes = probe_fns(state)
            for ws, fn in probes:
                jax.block_until_ready(fn(ws, state["factors"]))
        # the state the window starts from, for the check (this also
        # compiles the copy the window makes of its first sweep's factors)
        before = jax.device_get(copy_factors(state["factors"]))
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f}s, {setup_clock.seconds:.3f}s of it compiling "
        f"({setup_clock.compiles} programs)")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced \
        else None
    if traced:
        jax.profiler.start_trace(trace_dir)
    with CompileClock() as window_clock:
        state, first, sweeps, window_s = window(
            state, seconds, float(cell.traffic["ahead_s"]))
    if traced:
        for ws, fn in probes:
            for _ in range(PROBE_CALLS):
                with span("probe"):
                    jax.block_until_ready(fn(ws, state["factors"]))
        jax.profiler.stop_trace()
    sweep_ms = window_s / sweeps * 1e3
    log(f"window: {sweeps} sweeps in {window_s:.4f}s: {sweep_ms:.4f} ms per "
        f"sweep; fit {state['fit']:.9f}; {window_clock.compiles} compiles "
        f"inside the window")
    memory = memory_peak_bytes()

    reduced = None
    if traced:
        reduced = trace_mod.reduce_trace(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    t0 = time.perf_counter()
    with span("check"):
        nums = check_sweep(state["tensor"], before, first)
    log(f"check: {time.perf_counter() - t0:.3f}s; per-mode residuals "
        f"{nums['mode_residuals']}; fit {nums['fit']:.9f} vs exact "
        f"{nums['true_fit']:.9f}")
    ctx = {"kind": "sweeps", "trace": reduced, "probe_calls": PROBE_CALLS,
           "modes": len(cfg["dims"]), "dims": state["tensor"].dims,
           "nnz": state["tensor"].nnz, "rank": int(cfg["rank"]),
           "csf_sort_s": times["csf_sort_s"],
           "compile_s": setup_clock.seconds}
    return {"e2e": {"setup_s": setup_s, "sweep_ms": sweep_ms},
            "ctx": ctx, "memory": memory, "attempted": sweeps,
            "failed": 0, "reduced": reduced,
            "checks": {k: nums[k] for k in limits}}


def check_sweep(tensor, before, after: dict) -> dict:
    """The sweep from ``before`` (factors on the host) to ``after`` (a
    state) against the float64 reference."""
    ref = reference.Reference(tensor.inds, tensor.vals, tensor.dims)
    nums = reference.sweep_check(ref, before, after["factors"],
                                 after["lmbda"], after["fit"])
    return dict(nums, fit=float(after["fit"]))
