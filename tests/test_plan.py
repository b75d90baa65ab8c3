"""Per-mode planning: stats regimes, auto impl selection, registry
capabilities, the unified CSF workspace feeding every impl, and interface
parity between cp_als and dist_cp_als (the paper's §V-D finding as code)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SparseTensor, available_impls, build_csf,
                        build_workspace, cp_als, get_impl, init_factors,
                        mttkrp, random_sparse)
from repro.core.csf import CSF, build_csf_loop_reference
from repro.dist.collectives import make_mesh
from repro.plan import (CONTENTION_THRESHOLD, DecompPlan, mode_stats,
                        plan_decomposition)
from repro.utils.report import plan_report

KEY = jax.random.PRNGKey(3)

# mode 0: 8 rows (hot -> contention); mode 1: 5000 rows hit ~once each
# (collision-light but tile-padding-heavy); mode 2: in between.
SKEWED_DIMS = (8, 5000, 64)


def skewed_tensor(nnz=2000):
    return random_sparse(SKEWED_DIMS, nnz, KEY)


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------

def test_mode_stats_regimes():
    t = skewed_tensor()
    s0 = mode_stats(t, 0, block=512, row_tile=128)
    s1 = mode_stats(t, 1, block=512, row_tile=128)
    assert s0.collision_rate > CONTENTION_THRESHOLD
    assert s0.regime == "contention"
    assert s1.collision_rate < CONTENTION_THRESHOLD
    assert s1.regime == "no-lock"
    # the long uniform mode pays heavy tile padding; the hot mode almost none
    assert s1.padding_overhead > 0.5 > s0.padding_overhead


def test_mode_stats_bounds():
    t = skewed_tensor()
    for m in range(3):
        s = mode_stats(t, m, block=256, row_tile=64)
        assert 0.0 <= s.collision_rate <= 1.0
        assert 0.0 <= s.padding_overhead < 1.0
        assert s.rows == t.dims[m] and s.nnz == t.nnz
    with pytest.raises(ValueError):
        mode_stats(t, 3, block=256, row_tile=64)


# ---------------------------------------------------------------------------
# auto planning
# ---------------------------------------------------------------------------

def test_auto_picks_different_impls_per_mode():
    """The tentpole property: on a skewed tensor the auto policy provably
    selects different impls for different modes (contention -> sorted
    no-lock segment; collision-light/padding-heavy -> gather_scatter)."""
    t = skewed_tensor()
    plan = plan_decomposition(t, "auto", rank=8, backend="cpu")
    assert plan.impls[0] == "segment", plan.summary()
    assert plan.impls[1] == "gather_scatter", plan.summary()
    assert len(set(plan.impls)) > 1


def test_fixed_policy_pins_all_modes():
    t = skewed_tensor()
    plan = plan_decomposition(t, "segment", rank=4)
    assert plan.impls == ("segment",) * 3
    assert all(p.layout == "csf" for p in plan.modes)
    # longest-first mode order (what the distributed partitioner wants)
    assert plan.mode_order_by_length() == (1, 2, 0)
    # zero-overhead fixed planning skips the stats pass but keeps the report
    lean = plan_decomposition(t, "segment", rank=4, with_stats=False)
    assert all(p.stats is None for p in lean.modes)
    assert "**segment**" in plan_report(lean)
    with pytest.raises(ValueError, match="with_stats=False"):
        lean.mode_order_by_length()


def test_unknown_policy_lists_registry():
    with pytest.raises(ValueError, match="unknown impl"):
        plan_decomposition(skewed_tensor(), "nope")


def test_auto_candidates_respect_capabilities():
    names = available_impls(order=3, backend="cpu")
    assert "rowloop" not in names    # benchmark_only
    assert "dense" not in names      # oracle
    assert "pallas" not in names     # tpu-native, cpu backend
    assert set(names) >= {"gather_scatter", "segment"}
    assert "pallas" in available_impls(order=3, backend="tpu")


def test_calibrated_planning_measures_ms():
    t = skewed_tensor(nnz=600)
    plan = plan_decomposition(t, "auto", rank=4, backend="cpu",
                              calibrate=True)
    for p in plan.modes:
        assert all(v > 0.0 for v in p.costs.values())
        assert "measured" in p.reason


def test_plan_report_renders_modes():
    t = skewed_tensor()
    rep = plan_report(plan_decomposition(t, "auto", rank=8, backend="cpu"))
    assert "| mode |" in rep and "regime" in rep
    for p in plan_decomposition(t, "auto", rank=8, backend="cpu").modes:
        assert p.impl in rep


# ---------------------------------------------------------------------------
# unified workspace: every registered impl, one layout, dense parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", [3, 4])
def test_registered_impls_match_dense_on_unified_workspace(order):
    """All registered (non-oracle) impls consume the same CSF workspace —
    or the one shared linearized workspace for lin-layout impls — and
    agree with the dense oracle, at order 3 and 4."""
    from repro.core.linearized import build_linearized

    dims = (23, 17, 31, 11)[:order]
    t = random_sparse(dims, 400, KEY)
    factors = init_factors(t.dims, 6, KEY)
    names = available_impls(order=order)  # backend=None: includes pallas
    assert set(names) >= {"gather_scatter", "segment", "pallas", "linearized"}
    lin = build_linearized(t, block=64, row_tile=32)
    for mode in range(order):
        want = mttkrp(t, factors, mode, impl="dense")
        ws = build_csf(t, mode, block=64, row_tile=32)
        for name in names:
            layout = get_impl(name).layout
            x = lin if layout == "lin" else (ws if layout != "coo" else t)
            got = mttkrp(x, factors, mode, impl=name)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
                err_msg=f"impl={name} mode={mode} order={order}")


def test_csf_row_ids_globally_sorted():
    """Padding points at each tile's last real row, preserving the global
    sort the segment impl's indices_are_sorted fast path relies on."""
    t = random_sparse((200, 13, 77), 2000, KEY)
    for mode in range(3):
        csf = build_csf(t, mode, block=128, row_tile=64)
        rows = np.asarray(csf.row_ids)
        assert np.all(np.diff(rows) >= 0), f"mode {mode} not sorted"
        assert rows.max() < t.dims[mode]


def test_build_workspace_follows_plan_layouts():
    t = skewed_tensor()
    plan = plan_decomposition(t, "auto", rank=8, backend="cpu")
    ws = build_workspace(t, plan)
    for p, w in zip(plan.modes, ws):
        if p.layout == "csf":
            assert isinstance(w, CSF) and w.mode == p.mode
        else:
            assert w is t
    # legacy string interface still builds CSF replicas
    ws_legacy = build_workspace(t, "segment", block=128)
    assert all(isinstance(w, CSF) for w in ws_legacy)


# ---------------------------------------------------------------------------
# loop-reference build (order > 3 + shared assembly)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", [3, 4])
def test_loop_reference_matches_fast_build(order):
    dims = (14, 9, 11, 7)[:order]
    t = random_sparse(dims, 120, KEY)
    for mode in (0, order - 1):
        slow = build_csf_loop_reference(t, mode)
        fast = build_csf(t, mode)
        np.testing.assert_array_equal(np.asarray(slow.row_ids),
                                      np.asarray(fast.row_ids))
        np.testing.assert_array_equal(np.asarray(slow.other_ids),
                                      np.asarray(fast.other_ids))
        np.testing.assert_allclose(np.asarray(slow.vals),
                                   np.asarray(fast.vals))


def test_loop_reference_mode_out_of_range():
    with pytest.raises(ValueError, match="out of range"):
        build_csf_loop_reference(skewed_tensor(nnz=50), 5)


# ---------------------------------------------------------------------------
# drivers: cp_als / dist_cp_als share the plan interface
# ---------------------------------------------------------------------------

def test_cpals_auto_equals_explicit_plan():
    t = skewed_tensor(nnz=900)
    plan = plan_decomposition(t, "auto", rank=4, backend="cpu")
    d1 = cp_als(t, rank=4, niters=4, impl="auto", key=KEY)
    d2 = cp_als(t, rank=4, niters=4, plan=plan, key=KEY)
    np.testing.assert_array_equal(np.asarray(d1.fit), np.asarray(d2.fit))
    for a, b in zip(d1.factors, d2.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cpals_auto_agrees_with_fixed_impls():
    """auto's mixed per-mode execution is numerically equivalent to the
    fixed impls (same ALS math, different schedules)."""
    t = skewed_tensor(nnz=900)
    d_auto = cp_als(t, rank=4, niters=5, impl="auto", key=KEY)
    d_seg = cp_als(t, rank=4, niters=5, impl="segment", key=KEY)
    np.testing.assert_allclose(float(d_auto.fit), float(d_seg.fit),
                               rtol=0, atol=1e-4)


def test_dist_rejects_unsupported_impl():
    """dist_cp_als must refuse impls its shard_map body cannot express
    rather than silently substituting scatter-add."""
    from repro.core.distributed import dist_cp_als

    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="shard_map body"):
        dist_cp_als(skewed_tensor(nnz=50), 3, mesh, impl="pallas")


def test_default_interpret_matches_backend(monkeypatch):
    from repro.kernels import ops

    want = jax.default_backend() != "tpu"
    assert ops.default_interpret() is want
    # no silent interpretation on a backend the kernels do not target
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops.default_interpret()


def test_cpals_step_builder_executes_plan():
    from repro.core.gram import gram
    from repro.launch.steps import make_cpals_step

    t = skewed_tensor(nnz=600)
    plan = plan_decomposition(t, "auto", rank=4, backend="cpu")
    ws = build_workspace(t, plan)
    step = make_cpals_step(plan)
    factors = init_factors(t.dims, 4, KEY, dtype=t.vals.dtype)
    grams = tuple(gram(a) for a in factors)
    norm_x_sq = jnp.sum(t.vals.astype(jnp.float32) ** 2)
    factors, grams, lam, fit = step(ws, factors, grams, norm_x_sq,
                                    norm_kind="max")
    assert all(bool(jnp.all(jnp.isfinite(f))) for f in factors)
    assert 0.0 <= float(fit) <= 1.0
