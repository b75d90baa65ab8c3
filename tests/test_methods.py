"""The decomposition-method registry (repro.methods): registry semantics,
convergence floors for all four methods, nonnegativity, the dense HOOI
reference, streaming-vs-batch equivalence, monotone fits, and the
capability gates on the distributed/streaming drivers."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import exact_lowrank_tensor
from repro.core import cp_als, paper_dataset, random_sparse
from repro.methods import (DecompState, MethodSpec, available_methods,
                           cp_als_streaming, cp_nn_hals, fit, get_method,
                           register_method, tucker_hooi)

KEY = jax.random.PRNGKey(42)

ALS_FAMILY = ("cp_als", "cp_nn_hals", "tucker_hooi", "cp_als_streaming")


@pytest.fixture(scope="module")
def lowrank():
    kt, _ = jax.random.split(KEY)
    return exact_lowrank_tensor((12, 10, 8), 4, kt)


def _fit_kwargs(method):
    spec = get_method(method)
    kw = {"niters": {"cp_als": 60, "cp_als_streaming": 60,
                     "cp_nn_hals": 150, "tucker_hooi": 10}[method]}
    if spec.supports_streaming:
        kw["n_chunks"] = 4
    return kw


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_all_four_methods_registered():
    names = available_methods()
    for want in ALS_FAMILY:
        assert want in names, names


def test_available_methods_filters():
    assert available_methods(dist=True) == ("cp_als",)
    assert available_methods(streaming=True) == ("cp_als_streaming",)
    assert available_methods(nonnegative=True) == ("cp_nn_hals",)
    assert available_methods(family="tucker") == ("tucker_hooi",)


def test_get_method_unknown_lists_registry():
    with pytest.raises(ValueError, match="cp_als"):
        get_method("nope")


def test_register_method_validates():
    with pytest.raises(ValueError, match="family"):
        register_method(MethodSpec(name="x", fn=lambda: None, family="bad"))
    with pytest.raises(ValueError, match="kernel"):
        register_method(MethodSpec(name="x", fn=lambda: None, family="cp",
                                   kernel="bad"))


def test_fit_rejects_path_for_non_streaming_method():
    with pytest.raises(TypeError, match="streaming"):
        fit("nonexistent.tns", 4, method="cp_als")


# ---------------------------------------------------------------------------
# acceptance: every method reconstructs a dense-reconstructible tensor
# ---------------------------------------------------------------------------

# (dims, true rank): the order-3 tensor of the ``lowrank`` fixture, and
# fully observed tensors of order 4 and 5 with at most 400 cells
FULL_RANK_CASES = (
    [pytest.param((12, 10, 8), 4, m, id=m) for m in ALS_FAMILY]
    + [pytest.param(dims, 2, m, id=f"{m}-order{len(dims)}")
       for dims in ((5, 4, 4, 5), (4, 3, 3, 3, 3)) for m in ALS_FAMILY])


@pytest.mark.parametrize("dims, true_rank, method", FULL_RANK_CASES)
def test_methods_reach_fit_at_full_rank(dims, true_rank, method):
    """fit >= 0.99 on a fully-observed low-rank tensor of order 3, 4 or 5:
    Tucker at the true multilinear rank, the CP methods at 1.5x the true
    rank."""
    kt, ki = jax.random.split(KEY)
    t = exact_lowrank_tensor(dims, true_rank, kt)
    rank = ((true_rank,) * len(dims) if method == "tucker_hooi"
            else true_rank * 3 // 2)
    dec = fit(t, rank, method=method, key=ki, **_fit_kwargs(method))
    assert float(dec.fit) >= 0.99, (method, float(dec.fit))


def test_cp_nn_hals_factors_are_nonnegative(lowrank):
    _, ki = jax.random.split(KEY)
    dec = fit(lowrank, 6, method="cp_nn_hals", niters=30, key=ki)
    for m, a in enumerate(dec.factors):
        assert float(jnp.min(a)) >= 0.0, (m, float(jnp.min(a)))
    assert float(jnp.min(dec.lmbda)) >= 0.0


@pytest.mark.parametrize("method", ["cp_als", "cp_nn_hals",
                                    "cp_als_streaming"])
def test_monotone_nondecreasing_fit(lowrank, method):
    """ALS-family sweeps never decrease the fit (within 1e-6 tolerance):
    every block update exactly minimizes the objective over its block."""
    _, ki = jax.random.split(KEY)
    fits = []
    kw = {"n_chunks": 4} if get_method(method).supports_streaming else {}
    fit(lowrank, 4, method=method, niters=15, key=ki,
        checkpoint_cb=lambda s: fits.append(float(s.fit)), **kw)
    assert len(fits) == 15
    for a, b in zip(fits, fits[1:]):
        assert b >= a - 1e-6, fits


def test_monotone_nondecreasing_fit_hooi(lowrank):
    """HOOI is monotone in ||core|| too, but it is orthogonal iteration, not
    ALS: at the truncated-rank plateau the thin SVD's rotation wiggle puts
    ~1e-6-scale f32 noise on ||core||^2, so the tolerance is one decade
    looser than the ALS-family bound."""
    _, ki = jax.random.split(KEY)
    fits = []
    fit(lowrank, (3, 3, 3), method="tucker_hooi", niters=15, key=ki,
        checkpoint_cb=lambda s: fits.append(float(s.fit)))
    assert len(fits) == 15
    for a, b in zip(fits, fits[1:]):
        assert b >= a - 1e-5, fits


# ---------------------------------------------------------------------------
# tucker_hooi vs a dense HOOI reference
# ---------------------------------------------------------------------------

def dense_hooi_reference(x: np.ndarray, ranks, factors, niters: int):
    """Textbook dense HOOI with the same init/iteration order as the sparse
    driver (numpy throughout)."""
    order = x.ndim
    factors = [np.asarray(a) for a in factors]
    for _ in range(niters):
        for n in range(order):
            # mode-n TTMc: contract every other mode with U_m^T
            y = x
            for m in range(order - 1, -1, -1):
                if m == n:
                    continue
                y = np.moveaxis(
                    np.tensordot(factors[m].T, y, axes=(1, m)), 0, m)
            y_mat = np.moveaxis(y, n, 0).reshape(y.shape[n], -1)
            u, _, _ = np.linalg.svd(y_mat, full_matrices=False)
            factors[n] = u[:, : ranks[n]]
    # core from the final factors
    g = x
    for m in range(order - 1, -1, -1):
        g = np.moveaxis(np.tensordot(factors[m].T, g, axes=(1, m)), 0, m)
    return g, factors


def test_tucker_hooi_matches_dense_reference(lowrank):
    """Sparse (TTMc-kernel) HOOI and a dense numpy HOOI from the same init
    must agree on the core+factors reconstruction to 1e-4."""
    _, ki = jax.random.split(KEY)
    ranks = (4, 4, 4)
    dec = tucker_hooi(lowrank, ranks, niters=6, key=ki)

    # same init: replicate the driver's QR-of-normal seeding
    from repro.methods.tucker_hooi import _init_orthonormal

    init = _init_orthonormal(lowrank.dims, ranks, ki, jnp.float32)
    x = np.asarray(lowrank.to_dense())
    core_ref, factors_ref = dense_hooi_reference(x, ranks, init, niters=6)

    recon = np.asarray(dec.to_dense())
    recon_ref = core_ref
    for m, u in enumerate(factors_ref):
        recon_ref = np.moveaxis(
            np.tensordot(u, recon_ref, axes=(1, m)), 0, m)
    np.testing.assert_allclose(recon, recon_ref, rtol=1e-4, atol=1e-4)


def test_tucker_values_at_matches_dense(lowrank):
    dec = tucker_hooi(lowrank, (4, 4, 4), niters=6, key=KEY)
    dense = np.asarray(dec.to_dense())
    inds = np.asarray(lowrank.inds[:64])
    got = np.asarray(dec.values_at(lowrank.inds[:64]))
    want = dense[inds[:, 0], inds[:, 1], inds[:, 2]]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_tucker_rank_validation(lowrank):
    with pytest.raises(ValueError, match="exceeds mode length"):
        tucker_hooi(lowrank, (99, 4, 4), niters=1)
    with pytest.raises(ValueError, match="modes"):
        tucker_hooi(lowrank, (4, 4), niters=1)


def test_tucker_order4():
    t = random_sparse((9, 8, 7, 6), 400, KEY)
    dec = tucker_hooi(t, 3, niters=3, key=KEY)
    assert dec.core.shape == (3, 3, 3, 3)
    assert [a.shape for a in dec.factors] == [(9, 3), (8, 3), (7, 3), (6, 3)]
    assert 0.0 <= float(dec.fit) <= 1.0


# ---------------------------------------------------------------------------
# streaming vs batch
# ---------------------------------------------------------------------------

def test_streaming_matches_batch_on_paper_tensor():
    """cp_als_streaming over 4 chunks == batch cp_als fit within 1e-3 on the
    scaled paper tensor (the acceptance contract)."""
    key = jax.random.PRNGKey(3)
    t = paper_dataset("yelp", key, scale=0.002)
    batch = cp_als(t, rank=8, niters=10, impl="gather_scatter", key=key)
    streamed = cp_als_streaming(t, 8, niters=10, n_chunks=4, key=key)
    assert abs(float(streamed.fit) - float(batch.fit)) < 1e-3, (
        float(streamed.fit), float(batch.fit))


def test_streaming_from_tns_path(tmp_path, lowrank):
    """A .tns path streams chunk batches without a full-read materialization
    and reaches the same fit class as the in-memory split."""
    from repro.ingest import write_tns

    p = tmp_path / "t.tns"
    write_tns(p, lowrank)
    dec = cp_als_streaming(str(p), 6, niters=40, chunk_nnz=257, key=KEY)
    assert float(dec.fit) > 0.98, float(dec.fit)


def test_streaming_rejects_sorted_impls(lowrank):
    with pytest.raises(ValueError, match="sorted workspace"):
        cp_als_streaming(lowrank, 4, impl="segment")


def test_streaming_decay_validates(lowrank):
    with pytest.raises(ValueError, match="decay"):
        cp_als_streaming(lowrank, 4, decay=1.5)
    with pytest.raises(ValueError, match="decay"):
        cp_als_streaming(lowrank, 4, decay=0.0)


def test_streaming_decay_fold_discounts_old_chunks(lowrank):
    """decay < 1 decomposes the discounted stream: the fold stays stable
    and converges, and with a mild discount the fit stays near batch."""
    dec = cp_als_streaming(lowrank, 6, niters=40, n_chunks=4, decay=0.99,
                           key=KEY)
    assert np.isfinite(float(dec.fit))
    assert float(dec.fit) > 0.7, float(dec.fit)


# ---------------------------------------------------------------------------
# capability gates on the drivers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["cp_nn_hals", "tucker_hooi",
                                    "cp_als_streaming"])
def test_dist_rejects_non_dist_methods(method):
    from jax.sharding import Mesh
    from repro.core.distributed import dist_cp_als

    t = random_sparse((12, 10, 8), 200, KEY)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="supports_dist"):
        dist_cp_als(t, 4, mesh, method=method)


def test_dryrun_rejects_non_dist_methods():
    import os

    # importing dryrun sets XLA_FLAGS for its own subprocess fan-out; jax is
    # already initialized here, so snapshot/restore to keep the env clean
    saved = os.environ.get("XLA_FLAGS")
    try:
        from repro.launch.dryrun import run_cpals

        with pytest.raises(ValueError, match="supports_dist"):
            run_cpals("cpals-yelp", multi_pod=False, method="tucker_hooi")
    finally:
        if saved is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = saved


# ---------------------------------------------------------------------------
# planner integration (ttmc kernel) + report
# ---------------------------------------------------------------------------

def test_plan_ttmc_kernel(lowrank):
    from repro.plan import plan_decomposition

    plan = plan_decomposition(lowrank, "auto", rank=(16, 12, 12),
                              backend="cpu", kernel="ttmc")
    assert all(p.kernel == "ttmc" for p in plan.modes)
    assert all(p.impl in ("segment", "gather_scatter") for p in plan.modes)


def test_plan_report_method_column(lowrank):
    from repro.plan import plan_decomposition
    from repro.utils.report import plan_report

    plan = plan_decomposition(lowrank, "auto", rank=8, backend="cpu",
                              kernel="ttmc")
    rep = plan_report(plan, method="tucker_hooi")
    assert "method=tucker_hooi" in rep
    assert "tucker_hooi:ttmc" in rep


def test_ingested_roundtrip_through_fit(lowrank):
    """Ingested handles flow through fit() for every non-streaming method,
    and factors come back in original labels under a reordering."""
    from repro.ingest import ingest

    ing = ingest(lowrank, reorder="degree_sort")
    for method in ("cp_als", "cp_nn_hals", "tucker_hooi"):
        rank = (3, 3, 3) if method == "tucker_hooi" else 4
        dec = fit(ing, rank, method=method, niters=3, key=KEY)
        assert dec.factors[0].shape[0] == lowrank.dims[0]
        # reconstruction is queried in ORIGINAL coordinates
        vals = np.asarray(dec.values_at(lowrank.inds[:8]))
        assert np.all(np.isfinite(vals))


# ---------------------------------------------------------------------------
# with_fit regression (satellite): no fabricated 0.0 fit
# ---------------------------------------------------------------------------

def test_cp_als_with_fit_false_returns_nan_not_zero(lowrank):
    dec = cp_als(lowrank, rank=4, niters=3, key=KEY, with_fit=False)
    assert math.isnan(float(dec.fit)), (
        "with_fit=False must not report a fabricated fit of 0.0")


def test_cp_als_with_fit_false_keeps_restored_fit(lowrank):
    states = []
    cp_als(lowrank, rank=4, niters=4, key=KEY, checkpoint_cb=states.append)
    restored = states[-1]
    dec = cp_als(lowrank, rank=4, niters=6, key=KEY, state=restored,
                 with_fit=False)
    # the last *computed* fit (the restored one), not NaN and not 0.0
    assert float(dec.fit) == pytest.approx(float(restored.fit))


def test_cp_als_with_fit_false_rejects_tol(lowrank):
    with pytest.raises(ValueError, match="with_fit"):
        cp_als(lowrank, rank=4, niters=3, tol=1e-3, with_fit=False)


# ---------------------------------------------------------------------------
# repo hygiene (satellite): generated artifacts stay out of git
# ---------------------------------------------------------------------------

def test_gitignore_covers_generated_artifacts():
    """__pycache__ (src/tests/benchmarks/examples alike), benchmark JSONs
    and the ingest cache must all be gitignored, and `make clean` must
    exist to sweep them locally."""
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    ignored = (root / ".gitignore").read_text().split()
    for pattern in ("__pycache__/", "BENCH_*.json", ".cache/", ".pytest_cache/"):
        assert pattern in ignored, f"{pattern} missing from .gitignore"
    makefile = (root / "Makefile").read_text()
    assert "\nclean:" in makefile, "Makefile needs a clean target"
    for sweep in ("__pycache__", "BENCH_*.json"):
        assert sweep in makefile.split("\nclean:")[1], (
            f"make clean must remove {sweep}")


def test_make_cpals_step_with_fit_false_is_nan():
    from repro.core import build_workspace, gram, init_factors, resolve_plan
    from repro.core.cpals import _iteration

    t = random_sparse((10, 9, 8), 300, KEY)
    plan = resolve_plan(t, "segment", None, rank=4)
    ws = build_workspace(t, plan)
    factors = init_factors(t.dims, 4, KEY)
    grams = tuple(gram(a) for a in factors)
    nxs = jnp.sum(t.vals ** 2)
    *_, fit_val = _iteration(ws, factors, grams, nxs, impls=plan.impls,
                             norm_kind="max", with_fit=False)
    assert math.isnan(float(fit_val))
