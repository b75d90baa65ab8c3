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


def test_dryrun_mini_cell_and_roofline_parser():
    """Reduced arch through the real dry-run path on a small mesh; the HLO
    parser must find the data-parallel gradient all-reduce."""
    out = run_py("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.dist.collectives import make_mesh
        from repro import configs
        from repro.launch.mesh import rules_for, sharding_fn, batch_sharding
        from repro.launch.steps import make_train_step
        from repro.models import Model
        from repro.models.config import ShapeConfig
        from repro.models.params import axes_tree
        from repro.optim import OPTIMIZERS
        from repro.utils import roofline as RL
        from repro.launch.dryrun import _map_axes, _sds

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = configs.smoke_of(configs.get("llama3.2-3b"))
        cfg = dataclasses.replace(cfg, vocab=1024, d_model=128, d_ff=256,
                                  num_heads=8, num_kv_heads=2)
        shape = ShapeConfig("mini", 128, 8, "train")
        rules = rules_for(cfg)
        sfn = sharding_fn(mesh, rules)
        model = Model(cfg)
        params_abs = model.abstract(sfn)
        bshapes = configs.batch_shapes(cfg, shape)
        batch_abs = {k: _sds(sh, dt, batch_sharding(mesh, rules, kind, sh))
                     for k, (sh, dt, kind) in bshapes.items()}
        optimizer = OPTIMIZERS["adamw"]()
        opt_shapes = jax.eval_shape(optimizer.init, params_abs)
        opt_axes = optimizer.state_axes(axes_tree(model.param_specs()))
        opt_abs = _map_axes(opt_shapes, opt_axes,
                            lambda s, a: _sds(s.shape, s.dtype, sfn(a, s.shape)))
        fn = make_train_step(model, optimizer)
        lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(
            params_abs, opt_abs, batch_abs, _sds((), jnp.int32))
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        rl = RL.analyze(cost, hlo, n_chips=8, model_flops=6.0 * 1e6 * 1024,
                        kind="TPU v5 lite")
        print("flops", rl.flops, "colls", sorted(rl.collectives))
        assert rl.flops > 0 and rl.bytes_accessed > 0
        assert "all-reduce" in rl.collectives, rl.collectives
        assert rl.collectives["all-reduce"]["wire"] > 0
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes > 0
        print("MINI DRYRUN OK")
    """)
    assert "MINI DRYRUN OK" in out


def test_dist_cpals_dryrun_lowering():
    """Abstract lowering of the distributed CP-ALS iteration on a small mesh
    (same code path the production dry-run uses for cpals-* cells)."""
    out = run_py("""
        import jax
        from repro.dist.collectives import make_mesh
        from repro.core.distributed import build_dist_cpals_lowered
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        lowered, info = build_dist_cpals_lowered("cpals-yelp", mesh)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        assert cost["flops"] > 0
        hlo = compiled.as_text()
        assert "all-reduce" in hlo
        print("CPALS LOWER OK", info["local_cap"])
    """)
    assert "CPALS LOWER OK" in out


def test_grad_compression_equivalence():
    """int8+EF compressed training stays close to exact training."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.dist.compress import (compress_grads_int8,
                                         decompress_grads_int8,
                                         init_error_feedback)
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (16, 4))
        x = jax.random.normal(jax.random.fold_in(key, 1), (64, 16))
        y = x @ jax.random.normal(jax.random.fold_in(key, 2), (16, 4))
        def loss(w):
            return jnp.mean((x @ w - y) ** 2)
        w1 = w; w2 = w; ef = init_error_feedback({'w': w})
        for i in range(60):
            g1 = jax.grad(loss)(w1)
            w1 = w1 - 0.01 * g1
            g2 = jax.grad(loss)(w2)
            q, s, ef = compress_grads_int8({'w': g2}, ef)
            g2d = decompress_grads_int8(q, s)['w']
            w2 = w2 - 0.01 * g2d
        l1, l2 = float(loss(w1)), float(loss(w2))
        print("exact", l1, "compressed", l2)
        assert l2 < l1 * 1.5 + 1e-3
        print("COMPRESS OK")
    """, devices=1)
    assert "COMPRESS OK" in out


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


def test_ep_moe_matches_dense_dispatch():
    """Expert-parallel shard_map MoE == dense-dispatch oracle (fwd + grads)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.dist.collectives import make_mesh
        from repro.models.config import ModelConfig, MoEConfig
        from repro.models.moe import moe_ffn_ep, _moe_ffn_dense_dispatch, moe_specs
        from repro.models.params import init_params
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = ModelConfig(name="m", family="moe", pattern=("moe",),
                          num_layers=1, d_model=32, num_heads=2,
                          num_kv_heads=2, head_dim=16, d_ff=64, vocab=64,
                          moe=MoEConfig(num_experts=8, top_k=2, d_ff=32,
                                        num_shared=1, capacity_factor=8.0),
                          param_dtype="float32", compute_dtype="float32")
        p = init_params(moe_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32)) * 0.5
        ref, _ = _moe_ffn_dense_dispatch(p, cfg, x)
        out, _ = jax.jit(lambda p, x: moe_ffn_ep(p, cfg, x, mesh))(p, x)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-4
        g1 = jax.jit(jax.grad(lambda p, x: jnp.sum(
            moe_ffn_ep(p, cfg, x, mesh)[0] ** 2)))(p, x)
        g2 = jax.grad(lambda p, x: jnp.sum(
            _moe_ffn_dense_dispatch(p, cfg, x)[0] ** 2))(p, x)
        for k in ("wg", "wd", "router", "shared_wg"):
            assert float(jnp.max(jnp.abs(g1[k] - g2[k]))) < 1e-2, k
        print("EP OK")
    """)
    assert "EP OK" in out
