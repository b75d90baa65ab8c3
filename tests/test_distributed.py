"""Distributed CP-ALS + dry-run machinery, run in subprocesses with
xla_force_host_platform_device_count so the main pytest process keeps a
single device (per the dry-run isolation rule)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def run_py(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def test_dist_cpals_matches_single_device():
    """Medium-grained distributed CP-ALS == shared-memory CP-ALS (same init),
    on a 4x2 mesh of host devices."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.dist.collectives import make_mesh
        from repro.core import random_sparse, cp_als
        from repro.core.cpals import init_factors
        from repro.core.distributed import dist_cp_als
        mesh = make_mesh((4, 2), ("data", "model"))
        key = jax.random.PRNGKey(5)
        t = random_sparse((37, 23, 19), 1500, key)

        # single-device reference with the SAME (padded+zeroed) init
        i_p, j_p = 40, 24
        full = init_factors((i_p, j_p, 19), 5, jax.random.PRNGKey(0))
        from repro.core.coo import SparseTensor
        state_factors = (full[0][:37], full[1][:23], full[2])
        from repro.core.cpals import CPALSState
        st = CPALSState(state_factors, jnp.ones((5,)), jnp.array(0.0),
                        jnp.array(0.0), jnp.array(0, dtype=jnp.int32))
        ref = cp_als(t, rank=5, niters=6, state=st)

        factors, lam, fit = dist_cp_als(t, 5, mesh, niters=6,
                                        key=jax.random.PRNGKey(0))
        print("ref_fit", float(ref.fit), "dist_fit", float(fit))
        assert abs(float(ref.fit) - float(fit)) < 2e-3, (ref.fit, fit)
        for a, b in zip(ref.factors, factors):
            err = float(jnp.max(jnp.abs(a - b)))
            print("factor err", err)
            assert err < 5e-2
        print("DIST OK")
    """)
    assert "DIST OK" in out


def test_dist_cpals_multipod_mesh():
    """The pod axis joins the row partition: (pod=2, data=2, model=2)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.dist.collectives import make_mesh
        from repro.core import random_sparse
        from repro.core.distributed import dist_cp_als
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        t = random_sparse((29, 17, 13), 900, jax.random.PRNGKey(1))
        factors, lam, fit = dist_cp_als(t, 4, mesh, niters=4)
        assert all(bool(jnp.all(jnp.isfinite(f))) for f in factors)
        print("fit", float(fit))
        assert 0.0 < float(fit) <= 1.0
        print("MULTIPOD OK")
    """)
    assert "MULTIPOD OK" in out


def test_dist_cpals_dryrun_lowering():
    """Abstract lowering of the distributed CP-ALS iteration on a small mesh
    (same code path the production dry-run uses for cpals-* cells).  The
    roofline's HLO parser reads the compiled text's all-reduces: each mode's
    psum over its partial-row axes, so groups of 2 ('model'), 4 (the row
    axes 'pod' x 'data') and 8 (the whole mesh)."""
    out = run_py("""
        import jax
        from repro.dist.collectives import make_mesh
        from repro.core.distributed import build_dist_cpals_lowered
        from repro.utils import roofline as RL
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        lowered, info = build_dist_cpals_lowered("cpals-yelp", mesh)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        assert cost["flops"] > 0
        hlo = compiled.as_text()
        assert "all-reduce" in hlo
        groups = {c["group"] for c in RL.parse_collectives(hlo)
                  if c["kind"] == "all-reduce" and c["wire"] > 0}
        print("all-reduce groups", sorted(groups))
        assert {2, 4, 8} <= groups, groups
        print("CPALS LOWER OK", info["local_cap"])
    """)
    assert "CPALS LOWER OK" in out


def test_dist_cpals_shard_c_and_mode_order_equivalent():
    """The optimized mode-2 layout (shard_c) and auto mode ordering are
    numerically equivalent to the baseline distributed algorithm."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.dist.collectives import make_mesh
        from repro.core import random_sparse
        from repro.core.cpals import init_factors
        from repro.core.distributed import dist_cp_als
        mesh = make_mesh((4, 2), ("data", "model"))
        t = random_sparse((37, 23, 19), 1500, jax.random.PRNGKey(5))
        init = init_factors(t.dims, 5, jax.random.PRNGKey(0))
        f1, l1, fit1 = dist_cp_als(t, 5, mesh, niters=5, init=init)
        f2, l2, fit2 = dist_cp_als(t, 5, mesh, niters=5, init=init,
                                   shard_c=True)
        f3, l3, fit3 = dist_cp_als(t, 5, mesh, niters=5, init=init,
                                   shard_c=True, mode_order="auto")
        assert abs(float(fit1) - float(fit2)) < 1e-5
        assert abs(float(fit1) - float(fit3)) < 1e-5
        for a, b in zip(f1, f2):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4
        for a, b in zip(f1, f3):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4, \
                float(jnp.max(jnp.abs(a - b)))
        print("OPT EQUIV OK")
    """)
    assert "OPT EQUIV OK" in out


def test_dist_cpals_plan_interface():
    """dist_cp_als shares cp_als's planner interface: impl='auto' == an
    explicit DecompPlan, and the mixed local schedule stays numerically
    equivalent to the fixed scatter path."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.dist.collectives import make_mesh
        from repro.core import random_sparse
        from repro.core.cpals import init_factors
        from repro.core.distributed import dist_cp_als
        from repro.plan import plan_decomposition
        mesh = make_mesh((4, 2), ("data", "model"))
        t = random_sparse((37, 23, 19), 1500, jax.random.PRNGKey(5))
        init = init_factors(t.dims, 5, jax.random.PRNGKey(0))
        plan = plan_decomposition(t, "auto", rank=5,
                                  allow=("gather_scatter", "segment"))
        f1, l1, fit1 = dist_cp_als(t, 5, mesh, niters=4, init=init,
                                   impl="auto")
        f2, l2, fit2 = dist_cp_als(t, 5, mesh, niters=4, init=init,
                                   plan=plan)
        f3, l3, fit3 = dist_cp_als(t, 5, mesh, niters=4, init=init,
                                   impl="gather_scatter")
        assert abs(float(fit1) - float(fit2)) < 1e-6, (fit1, fit2)
        assert abs(float(fit1) - float(fit3)) < 1e-3, (fit1, fit3)
        for a, b in zip(f1, f2):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-6
        print("PLAN IFACE OK", plan.summary())
    """)
    assert "PLAN IFACE OK" in out
