"""CP-ALS core correctness: MTTKRP variants vs dense oracle, Alg. 1 semantics,
convergence on synthetic low-rank tensors (the paper's correctness floor)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    random_sparse, from_factors, build_csf, build_csf_tiled,
    mttkrp, cp_als, init_factors, gram, hadamard_grams, solve_cholesky,
    solve_gram, normalize, kruskal_fit,
)
from repro.core.gram import GRAM_ROWS

KEY = jax.random.PRNGKey(42)


def small_tensor(order=3, skew=0.0, nnz=500, key=KEY):
    dims = (23, 17, 31, 11)[:order]
    return random_sparse(dims, nnz, key, skew=skew)


# ---------------------------------------------------------------------------
# MTTKRP variants vs the dense oracle
# ---------------------------------------------------------------------------

# the impls that read the sorted CSF workspace; the others take the COO tensor
CSF_IMPLS = ("segment", "pallas")


@pytest.mark.parametrize("impl", ["gather_scatter", "segment", "rowloop",
                                  "pallas"])
@pytest.mark.parametrize("mode", [0, 1, 2])
@pytest.mark.parametrize("skew", [0.0, 1.5])
def test_mttkrp_matches_dense(impl, mode, skew):
    t = small_tensor(skew=skew)
    factors = init_factors(t.dims, 8, KEY)
    want = mttkrp(t, factors, mode, impl="dense")
    x = build_csf(t, mode, block=64) if impl in CSF_IMPLS else t
    got = mttkrp(x, factors, mode, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


# tensors of order 4 and 5 small enough for the dense oracle (dims <= 12)
HIGH_ORDER_DIMS = {4: (12, 9, 11, 7), 5: (12, 9, 11, 7, 10)}


@pytest.mark.parametrize("impl", ["gather_scatter", "segment", "rowloop",
                                  "pallas"])
@pytest.mark.parametrize("order, mode",
                         [(o, m) for o in (4, 5) for m in range(o)])
def test_mttkrp_high_order_matches_dense(order, mode, impl):
    """The paper limits itself to 3rd order; every impl takes orders 4 and 5
    (the pallas kernel forms the whole Khatri-Rao product of 3 or 4
    gathered operands) and matches the dense oracle on skewed keys."""
    t = random_sparse(HIGH_ORDER_DIMS[order], 400, KEY, skew=1.5)
    factors = init_factors(t.dims, 5, KEY)
    want = mttkrp(t, factors, mode, impl="dense")
    x = build_csf(t, mode, block=64) if impl in CSF_IMPLS else t
    got = mttkrp(x, factors, mode, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("order, mode",
                         [(o, m) for o in (4, 5) for m in range(o)])
def test_build_csf_keeps_each_nonzero_once(order, mode):
    """The mode-sorted workspace of an order-4 or -5 tensor holds every
    non-zero exactly once (padding entries carry value 0), its rows in
    non-decreasing order."""
    t = random_sparse(HIGH_ORDER_DIMS[order], 400, KEY, skew=1.5)
    csf = build_csf(t, mode, block=32)
    rows = np.asarray(csf.row_ids)
    vals = np.asarray(csf.vals)
    assert np.all(np.diff(rows) >= 0)
    real = vals != 0.0
    coords = np.empty((int(real.sum()), order), np.int64)
    coords[:, mode] = rows[real]
    coords[:, [m for m in range(order) if m != mode]] = \
        np.asarray(csf.other_ids)[real]
    got = sorted(zip(map(tuple, coords.tolist()), vals[real].tolist()))
    want = sorted(zip(map(tuple, np.asarray(t.inds[:t.nnz]).tolist()),
                      np.asarray(t.vals[:t.nnz]).tolist()))
    assert got == want


def test_mttkrp_padding_is_noop():
    t = small_tensor()
    factors = init_factors(t.dims, 8, KEY)
    base = mttkrp(t, factors, 0, impl="gather_scatter")
    padded = t.pad_to(256)
    got = mttkrp(padded, factors, 0, impl="gather_scatter")
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-5)


# ---------------------------------------------------------------------------
# dense linear algebra pieces
# ---------------------------------------------------------------------------

def test_solve_cholesky_matches_lstsq():
    k1, k2 = jax.random.split(KEY)
    a = jax.random.normal(k1, (40, 8))
    v = a.T @ a + 0.1 * jnp.eye(8)
    m = jax.random.normal(k2, (30, 8))
    got = solve_cholesky(m, v)
    want = m @ jnp.linalg.inv(v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4)


def test_solve_gram_matches_solve_cholesky():
    """The fused epilogue's inverse-then-GEMM solve agrees with the
    triangular-solve formulation on tall right-hand sides."""
    k1, k2 = jax.random.split(KEY, 2)
    a = jax.random.normal(k1, (60, 12))
    v = a.T @ a + 0.1 * jnp.eye(12)
    m = jax.random.normal(k2, (500, 12))
    got = solve_gram(m, v)
    want = solve_cholesky(m, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("kind", ["max", "2"])
def test_normalize_reconstruction_invariant(kind):
    """normalize() must not change lambda-weighted reconstruction."""
    a = jax.random.uniform(KEY, (20, 6)) + 0.1
    an, lam = normalize(a, kind=kind)
    np.testing.assert_allclose(np.asarray(an * lam[None, :]), np.asarray(a), rtol=1e-5)


def test_hadamard_grams_skips_mode():
    gs = [jnp.full((3, 3), float(i + 2)) for i in range(3)]
    v = hadamard_grams(gs, 1)
    np.testing.assert_allclose(np.asarray(v), np.full((3, 3), 2.0 * 4.0))


@pytest.mark.parametrize("rows", [1, 7, GRAM_ROWS - 1, 512, 513,
                                  2 * GRAM_ROWS, 2 * GRAM_ROWS + 1, 1500,
                                  4 * GRAM_ROWS, 100_000])
def test_gram_matches_float64(rows):
    """A Gram within float32 round-off of float64 at any height (read 3.8e-8
    to 1.5e-7 here), the same from an eager call as from a jitted one: a
    resumed fit recomputes its Grams eagerly."""
    a = jax.random.normal(jax.random.PRNGKey(rows), (rows, 35))
    a64 = np.asarray(a, np.float64)
    want = a64.T @ a64
    got = np.asarray(gram(a))
    assert np.linalg.norm(got - want) <= 1e-6 * np.linalg.norm(want)
    np.testing.assert_array_equal(got, np.asarray(jax.jit(gram)(a)))


def test_tall_gram_contracts_chunks_of_rows():
    """A factor taller than ``GRAM_ROWS`` is contracted ``GRAM_ROWS`` rows
    at a time (the last chunk zero-padded), never over all its rows."""
    rows = 10 * GRAM_ROWS - 3
    text = jax.jit(gram).lower(jnp.zeros((rows, 35))).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert dots and all(f"tensor<10x{GRAM_ROWS}x35xf32>" in line
                        for line in dots), dots


# ---------------------------------------------------------------------------
# CP-ALS end to end
# ---------------------------------------------------------------------------

from conftest import exact_lowrank_tensor  # noqa: E402 — shared construction


@pytest.mark.parametrize("impl", ["gather_scatter", "segment"])
def test_cpals_converges_on_exact_lowrank(impl):
    """fit -> ~1 on a fully-observed rank-4 tensor decomposed at rank 6."""
    kt, ki = jax.random.split(KEY)
    t = exact_lowrank_tensor((12, 10, 8), 4, kt)
    dec = cp_als(t, rank=6, niters=60, impl=impl, key=ki)
    assert float(dec.fit) > 0.98, f"fit {float(dec.fit)} too low"


def test_cpals_fit_monotone_tail():
    """ALS fit should be (weakly) increasing after the first iterations."""
    t = small_tensor(nnz=800)
    fits = []
    for n in (3, 6, 9):
        dec = cp_als(t, rank=4, niters=n, key=KEY)
        fits.append(float(dec.fit))
    assert fits[0] <= fits[1] + 1e-4 and fits[1] <= fits[2] + 1e-4, fits


def test_cpals_reconstruction_error_matches_fit():
    """fit reported by the inner-product trick == fit computed from a dense
    reconstruction (validates SPLATT's work-free fit formula)."""
    t = small_tensor(nnz=700)
    dec = cp_als(t, rank=5, niters=10, key=KEY)
    dense_x = np.asarray(t.to_dense())
    dense_hat = np.asarray(dec.to_dense())
    fro = np.linalg.norm(dense_x - dense_hat)
    fit_direct = 1.0 - fro / np.linalg.norm(dense_x)
    assert abs(float(dec.fit) - fit_direct) < 1e-3


def test_cpals_timers_cover_routines():
    t = small_tensor(nnz=400)
    timers = {}
    cp_als(t, rank=4, niters=3, key=KEY, timers=timers)
    for k in ("sort", "mttkrp", "ata", "inverse", "norm", "fit"):
        assert k in timers and timers[k] >= 0.0, (k, timers)


def test_cpals_state_restart_is_deterministic():
    """Fault-tolerance contract: restarting from a checkpointed CPALSState
    reproduces the uninterrupted run exactly (same iterates)."""
    t = small_tensor(nnz=600)
    states = []
    full = cp_als(t, rank=4, niters=8, key=KEY, checkpoint_cb=states.append)
    mid = states[3]  # state after iteration 4
    resumed = cp_als(t, rank=4, niters=8, key=KEY, state=mid)
    for a, b in zip(full.factors, resumed.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(full.lmbda), np.asarray(resumed.lmbda))


def test_cpals_tolerance_early_stop():
    t = small_tensor(nnz=500)
    dec = cp_als(t, rank=4, niters=100, tol=1e-3, key=KEY)
    # must have stopped early and still produce a sane fit
    assert 0.0 <= float(dec.fit) <= 1.0


def test_values_at_matches_dense():
    t = small_tensor(nnz=300)
    dec = cp_als(t, rank=4, niters=5, key=KEY)
    dense = np.asarray(dec.to_dense())
    inds = np.asarray(t.inds[:50])
    got = np.asarray(dec.values_at(t.inds[:50]))
    want = dense[inds[:, 0], inds[:, 1], inds[:, 2]]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
