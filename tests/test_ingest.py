"""repro.ingest: streaming readers, invertible relabelings, the
content-addressed workspace cache, and the drivers' Ingested interface."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SparseTensor, cp_als, init_factors, random_sparse
from repro.core.cpals import CPALSState
from repro.dist.collectives import make_mesh
from repro.ingest import (IngestCache, Ingested, Relabeling, compact,
                          content_key, convert_tns, degree_sort, ingest,
                          random_block, read_tns, read_tnsb, write_tns,
                          write_tnsb)
from repro.plan import plan_decomposition
from repro.plan.stats import measured_block_collision, tensor_stats
from repro.utils.report import plan_report

KEY = jax.random.PRNGKey(3)
# the skewed shape test_plan.py uses: mode 0 hot, mode 1 long/uniform
SKEWED_DIMS = (8, 5000, 64)


def skewed_tensor(nnz=2000):
    return random_sparse(SKEWED_DIMS, nnz, KEY)


def small_tensor(nnz=300, dims=(17, 23, 9)):
    return random_sparse(dims, nnz, KEY)


# ---------------------------------------------------------------------------
# reader: .tns text
# ---------------------------------------------------------------------------

def test_read_tns_tolerates_comments_and_blanks(tmp_path):
    p = tmp_path / "x.tns"
    p.write_text(
        "# a FROSTT comment\n"
        "\n"
        "1 1 1 2.5\n"
        "% matrix-market-style comment\n"
        "  \t \n"
        "2 3 1 -1.0\n")
    t = read_tns(p)
    assert t.dims == (2, 3, 1) and t.nnz == 2
    assert np.allclose(np.asarray(t.vals), [2.5, -1.0])


def test_read_tns_rejects_ragged_arity(tmp_path):
    p = tmp_path / "x.tns"
    p.write_text("1 1 1 2.5\n1 2 0.5\n")
    with pytest.raises(ValueError, match="x.tns:2.*expected 4 fields"):
        read_tns(p)


def test_read_tns_rejects_non_numeric_and_zero_index(tmp_path):
    p = tmp_path / "x.tns"
    p.write_text("1 1 1 abc\n")
    with pytest.raises(ValueError, match="non-numeric"):
        read_tns(p)
    p.write_text("0 1 1 2.0\n")
    with pytest.raises(ValueError, match="1-based"):
        read_tns(p)


def test_read_tns_explicit_dims_keeps_empty_slices(tmp_path):
    p = tmp_path / "x.tns"
    p.write_text("1 1 1 1.0\n2 2 2 2.0\n")
    assert read_tns(p).dims == (2, 2, 2)  # inferred: shrinks
    t = read_tns(p, dims=(5, 2, 7))       # explicit: kept
    assert t.dims == (5, 2, 7)
    with pytest.raises(ValueError, match="out of range"):
        read_tns(p, dims=(1, 2, 2))
    with pytest.raises(ValueError, match="has 2 modes"):
        read_tns(p, dims=(2, 2))


def test_read_tns_duplicate_policies(tmp_path):
    p = tmp_path / "x.tns"
    p.write_text("1 1 1 1.0\n1 1 1 2.0\n2 1 1 4.0\n")
    t_sum = read_tns(p)  # default "sum"
    assert t_sum.nnz == 2
    assert np.isclose(float(t_sum.to_dense()[0, 0, 0]), 3.0)
    t_keep = read_tns(p, duplicates="keep")
    assert t_keep.nnz == 3
    with pytest.raises(ValueError, match="duplicate"):
        read_tns(p, duplicates="error")
    with pytest.raises(ValueError, match="policy"):
        read_tns(p, duplicates="nope")


def test_read_tns_streams_in_chunks(tmp_path):
    t = small_tensor()
    p = tmp_path / "x.tns"
    write_tns(p, t)
    t2 = read_tns(p, dims=t.dims, chunk_lines=7)  # many tiny chunks
    np.testing.assert_allclose(np.asarray(t2.to_dense()),
                               np.asarray(t.to_dense()), rtol=1e-6)


def _assert_tns_roundtrip_bit_exact(t, path):
    write_tns(path, t)
    t2 = read_tns(path, dims=t.dims, duplicates="keep")
    assert t2.nnz == t.nnz and t2.order == t.order
    lin = lambda x: np.ravel_multi_index(
        tuple(np.asarray(x.inds)[:, m] for m in range(t.order)), t.dims)
    a = np.asarray(t.vals)[np.argsort(lin(t))]
    b = np.asarray(t2.vals)[np.argsort(lin(t2))]
    np.testing.assert_array_equal(np.sort(lin(t)), np.sort(lin(t2)))
    np.testing.assert_array_equal(a, b)


def test_write_read_tns_roundtrip_bit_exact(tmp_path):
    """The vectorized writer emits enough digits that every float32 value
    survives the text roundtrip bit-exactly."""
    _assert_tns_roundtrip_bit_exact(small_tensor(nnz=500), tmp_path / "x.tns")


@pytest.mark.parametrize("dims", [(12, 9, 11, 7), (12, 9, 11, 7, 10)])
def test_write_read_tns_roundtrip_bit_exact_high_order(tmp_path, dims):
    """An order-4 or -5 tensor's lines carry one index per mode; its
    coordinates and float32 values come back bit-exactly."""
    _assert_tns_roundtrip_bit_exact(small_tensor(nnz=400, dims=dims),
                                    tmp_path / "x.tns")


# ---------------------------------------------------------------------------
# reader: .tnsb binary
# ---------------------------------------------------------------------------

def test_tnsb_roundtrip_and_convert(tmp_path):
    t = small_tensor()
    pb = tmp_path / "x.tnsb"
    write_tnsb(pb, t)
    for mmap in (True, False):
        t2 = read_tnsb(pb, mmap=mmap)
        assert t2.dims == t.dims and t2.nnz == t.nnz
        np.testing.assert_array_equal(np.asarray(t2.inds),
                                      np.asarray(t.inds[: t.nnz]))
        np.testing.assert_array_equal(np.asarray(t2.vals),
                                      np.asarray(t.vals[: t.nnz]))
    # text -> binary conversion
    pt = tmp_path / "x.tns"
    write_tns(pt, t)
    t3 = convert_tns(pt, tmp_path / "c.tnsb", dims=t.dims)
    t4 = read_tnsb(tmp_path / "c.tnsb")
    np.testing.assert_allclose(np.asarray(t4.to_dense()),
                               np.asarray(t.to_dense()), rtol=1e-6)
    assert t3.dims == t.dims


def test_tnsb_rejects_garbage(tmp_path):
    p = tmp_path / "bad.tnsb"
    p.write_bytes(b"not a tensor at all, but long enough for a header")
    with pytest.raises(ValueError, match="magic"):
        read_tnsb(p)
    p.write_bytes(b"shrt")
    with pytest.raises(ValueError, match="truncated"):
        read_tnsb(p)


# ---------------------------------------------------------------------------
# relabel: invertibility, composition, factor mapping
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("maker", [degree_sort, random_block, compact],
                         ids=["degree_sort", "random_block", "compact"])
def test_relabel_inverse_roundtrip(maker):
    t = skewed_tensor(nnz=800)
    rel = maker(t)
    t2 = rel.apply(t)
    t3 = rel.invert().apply(t2)
    np.testing.assert_array_equal(np.asarray(t3.inds),
                                  np.asarray(t.inds[: t.nnz]))
    np.testing.assert_array_equal(np.asarray(t3.vals),
                                  np.asarray(t.vals[: t.nnz]))
    # the relabeled tensor is the same tensor under a row bijection
    assert t2.nnz == t.nnz
    assert float(t2.norm()) == pytest.approx(float(t.norm()), rel=1e-6)


def test_compact_drops_empty_slices():
    t = skewed_tensor()
    rel = compact(t)
    t2 = rel.apply(t)
    assert t2.dims[1] < t.dims[1]  # 5000 rows, 2000 nnz -> empties dropped
    counts = np.bincount(np.asarray(t2.inds)[:, 1], minlength=t2.dims[1])
    assert counts.min() > 0


def test_relabel_compose_matches_sequential():
    t = skewed_tensor(nnz=600)
    r1 = compact(t)
    t_mid = r1.apply(t)
    r2 = degree_sort(t_mid)
    combined = r1.then(r2)
    a = r2.apply(r1.apply(t))
    b = combined.apply(t)
    np.testing.assert_array_equal(np.asarray(a.inds), np.asarray(b.inds))
    np.testing.assert_array_equal(np.asarray(a.vals), np.asarray(b.vals))
    # and the composite still inverts exactly
    t3 = combined.invert().apply(b)
    np.testing.assert_array_equal(np.asarray(t3.inds),
                                  np.asarray(t.inds[: t.nnz]))


def test_factor_map_roundtrip():
    t = skewed_tensor(nnz=600)
    rel = degree_sort(t)
    factors = init_factors(t.dims, 5, KEY)
    back = rel.restore_factors(rel.apply_factors(factors))
    for a, b in zip(factors, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_factors_zero_fills_dropped_slices():
    t = skewed_tensor()
    rel = compact(t)
    f2 = init_factors(rel.dims_new, 4, KEY)
    restored = rel.restore_factors(f2)
    assert restored[1].shape[0] == t.dims[1]
    empty = np.setdiff1d(np.arange(t.dims[1]),
                         np.asarray(rel.old_of_new[1]))
    assert np.all(np.asarray(restored[1])[empty] == 0.0)


# ---------------------------------------------------------------------------
# degree_sort reduces the measured intra-block collision (acceptance)
# ---------------------------------------------------------------------------

def test_degree_sort_reduces_measured_collision():
    """On the skewed tensor the contention-aware relinearization strictly
    reduces the planner's measured intra-block collision rate — on the mode
    it targets and in the cross-mode mean."""
    t = skewed_tensor()
    before = tensor_stats(t, block=512, row_tile=128)
    rel = degree_sort(t)
    after = tensor_stats(rel.apply(t), block=512, row_tile=128)
    m = rel.linearized_mode
    assert m is not None
    assert (after[m].block_collision_rate
            < before[m].block_collision_rate), (m, before[m], after[m])
    mean_b = np.mean([s.block_collision_rate for s in before])
    mean_a = np.mean([s.block_collision_rate for s in after])
    assert mean_a < mean_b
    # the histogram *expectation* is relabeling-invariant — sanity-check the
    # two stats really are different quantities
    for b, a in zip(before, after):
        assert a.collision_rate == pytest.approx(b.collision_rate, abs=1e-9)


def test_measured_block_collision_bounds():
    assert measured_block_collision(np.array([], dtype=np.int64), 8) == 0.0
    assert measured_block_collision(np.zeros(64, dtype=np.int64), 8) == \
        pytest.approx(1.0 - 8 / 64)
    distinct = np.arange(64)
    assert measured_block_collision(distinct, 8) == 0.0


# ---------------------------------------------------------------------------
# cache: content addressing, warm hits skip the build
# ---------------------------------------------------------------------------

def test_cache_warm_hit_skips_build_and_stats(tmp_path, monkeypatch):
    t = skewed_tensor()
    cold = ingest(t, reorder="degree_sort", cache=tmp_path / "c")
    assert not cold.cache_hit and cold.cache.misses == 1
    assert sorted(cold._csf) == [0, 1, 2]  # ALLMODE prebuild

    # a warm ingest must perform ZERO workspace builds
    import repro.core.csf as csf_mod
    calls = []
    real = csf_mod.build_csf
    monkeypatch.setattr(csf_mod, "build_csf",
                        lambda *a, **k: calls.append(a) or real(*a, **k))
    warm = ingest(t, reorder="degree_sort", cache=tmp_path / "c")
    assert warm.cache_hit and warm.cache.hits == 1
    assert calls == []

    # and the cached state is bit-identical to the cold one
    np.testing.assert_array_equal(np.asarray(warm.tensor.inds),
                                  np.asarray(cold.tensor.inds))
    assert warm.stats == cold.stats
    assert warm.stats_before == cold.stats_before
    assert warm.relabeling is not None
    for m in range(3):
        np.testing.assert_array_equal(
            np.asarray(warm._csf[m].row_ids),
            np.asarray(cold._csf[m].row_ids))


def test_cache_key_separates_options(tmp_path):
    t = skewed_tensor(nnz=200)
    k1 = content_key(t, block=512, row_tile=128)
    k2 = content_key(t, block=256, row_tile=128)
    k3 = content_key(t, block=512, row_tile=128, reorder="degree_sort")
    assert len({k1, k2, k3}) == 3
    t2 = SparseTensor(inds=t.inds, vals=t.vals * 2.0, dims=t.dims, nnz=t.nnz)
    assert content_key(t2, block=512, row_tile=128) != k1


def test_cache_key_of_file_matches_warm_path(tmp_path):
    t = small_tensor()
    p = tmp_path / "x.tnsb"
    write_tnsb(p, t)
    c = tmp_path / "c"
    cold = ingest(p, cache=c)
    warm = ingest(p, cache=c)
    assert not cold.cache_hit and warm.cache_hit
    assert warm.source == str(p)
    np.testing.assert_array_equal(np.asarray(warm.tensor.inds),
                                  np.asarray(t.inds[: t.nnz]))


def test_cpals_same_result_cold_and_warm(tmp_path):
    t = skewed_tensor(nnz=600)
    d1 = cp_als(ingest(t, cache=tmp_path / "c"), rank=4, niters=3, key=KEY)
    d2 = cp_als(ingest(t, cache=tmp_path / "c"), rank=4, niters=3, key=KEY)
    np.testing.assert_array_equal(np.asarray(d1.fit), np.asarray(d2.fit))
    for a, b in zip(d1.factors, d2.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# planner integration: ingest-time stats are reused
# ---------------------------------------------------------------------------

def test_plan_reuses_ingest_stats(monkeypatch):
    t = skewed_tensor()
    ing = ingest(t)
    ref = plan_decomposition(t, "auto", rank=8, backend="cpu")
    import repro.plan.planner as planner_mod
    monkeypatch.setattr(
        planner_mod, "mode_stats",
        lambda *a, **k: pytest.fail("planner re-measured stats"))
    plan = ing.plan("auto", rank=8, backend="cpu")
    assert plan.impls == ref.impls


def test_plan_rejects_mismatched_stats_geometry():
    t = skewed_tensor()
    stats = tuple(tensor_stats(t, block=256, row_tile=64))
    with pytest.raises(ValueError, match="block=256"):
        plan_decomposition(t, "auto", backend="cpu", stats=stats,
                           block=512, row_tile=128)
    with pytest.raises(ValueError, match="cover"):
        plan_decomposition(t, "auto", backend="cpu", stats=stats[:2])


def test_ingested_workspace_follows_plan():
    from repro.core.csf import CSF

    t = skewed_tensor()
    ing = ingest(t)
    plan = ing.plan("auto", rank=8, backend="cpu")
    ws = ing.workspace(plan)
    for p, w in zip(plan.modes, ws):
        if p.layout == "csf":
            assert isinstance(w, CSF) and w.mode == p.mode
        else:
            assert w is ing.tensor
    with pytest.raises(ValueError, match="tile"):
        bad = plan_decomposition(t, "segment", block=64, row_tile=32)
        ing.workspace(bad)


def test_plan_report_shows_reorder_deltas():
    t = skewed_tensor()
    ing = ingest(t, reorder="degree_sort")
    rep = plan_report(ing.plan("auto", rank=8, backend="cpu"),
                      reorder_deltas=ing.reorder_deltas())
    assert "reorder" in rep and "coll" in rep
    # identity ingest has no deltas; column renders as "-"
    rep2 = plan_report(ingest(t).plan("auto", rank=8, backend="cpu"))
    assert "reorder" in rep2


# ---------------------------------------------------------------------------
# end-to-end: reordered decomposition == natural, in original labels
# ---------------------------------------------------------------------------

def test_cpals_reordered_matches_natural_e2e():
    """CP-ALS on a degree_sort-reordered tensor, with factors mapped back
    through the inverse relabeling, matches the natural-order run: fit to
    1e-5 and factors elementwise (the ALS update is equivariant under row
    relabelings; only f32 reduction order differs)."""
    t = skewed_tensor(nnz=900)
    rank, niters = 4, 4
    f0 = init_factors(t.dims, rank, KEY, dtype=t.vals.dtype)

    def state_of(factors):
        r = jnp.ones((rank,), dtype=t.vals.dtype)
        z = jnp.array(0.0, dtype=t.vals.dtype)
        return CPALSState(tuple(factors), r, z, z,
                          jnp.array(0, dtype=jnp.int32))

    d_nat = cp_als(t, rank, niters=niters, impl="segment", key=KEY,
                   state=state_of(f0))

    ing = ingest(t, reorder="degree_sort")
    d_re = cp_als(ing, rank, niters=niters, impl="segment", key=KEY,
                  state=state_of(ing.relabeling.apply_factors(f0)))

    assert abs(float(d_nat.fit) - float(d_re.fit)) < 1e-5
    for a, b in zip(d_nat.factors, d_re.factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_cpals_compacted_restores_original_labels():
    t = skewed_tensor(nnz=600)
    ing = ingest(t, compact=True)
    assert ing.dims[1] < t.dims[1]
    dec = cp_als(ing, rank=4, niters=3, key=KEY)
    # factors come back in the ORIGINAL label space
    assert ing.original_dims == t.dims
    for m, f in enumerate(dec.factors):
        assert f.shape[0] == t.dims[m]
    # empty slices reconstruct to zero
    empty = np.setdiff1d(np.arange(t.dims[1]),
                         np.asarray(t.inds[: t.nnz, 1]))
    coords = np.zeros((len(empty), 3), dtype=np.int32)
    coords[:, 1] = empty
    np.testing.assert_allclose(np.asarray(dec.values_at(jnp.asarray(coords))),
                               0.0, atol=1e-6)


def test_dist_cpals_accepts_ingested():
    from repro.core.distributed import dist_cp_als

    t = skewed_tensor(nnz=400)
    mesh = make_mesh((1, 1), ("data", "model"))
    f_nat, lam_nat, fit_nat = dist_cp_als(t, 3, mesh, niters=2, key=KEY)
    ing = ingest(t, reorder="degree_sort")
    f_re, lam_re, fit_re = dist_cp_als(ing, 3, mesh, niters=2, key=KEY)
    assert f_re[0].shape == f_nat[0].shape  # original label space
    for f, d in zip(f_re, t.dims):
        assert f.shape[0] == d
    assert np.isfinite(float(fit_re))


def test_ingest_rejects_unknown_reorder():
    with pytest.raises(ValueError, match="unknown reorder"):
        ingest(skewed_tensor(nnz=50), reorder="nope")
    with pytest.raises(TypeError, match="SparseTensor or repro.ingest"):
        cp_als([1, 2, 3], rank=2)


def test_cache_key_includes_reader_options(tmp_path):
    """Different dims=/duplicates= reader settings must not share a cache
    entry (a warm hit would silently return the wrong tensor)."""
    t = small_tensor()
    p = tmp_path / "x.tns"
    write_tns(p, t)
    c = tmp_path / "c"
    a = ingest(p, cache=c)
    b = ingest(p, cache=c, dims=(40, 40, 40))
    assert not b.cache_hit and b.tensor.dims == (40, 40, 40)
    k = ingest(p, cache=c, duplicates="keep")
    assert not k.cache_hit


def test_read_any_tnsb_honors_dims_and_duplicates(tmp_path):
    from repro.ingest import read_any

    t = small_tensor()
    p = tmp_path / "x.tnsb"
    write_tnsb(p, t)
    with pytest.raises(ValueError, match="header says dims"):
        read_any(p, dims=(40, 40, 40))
    # a tnsb with duplicate coordinates trips the error policy
    dup = SparseTensor(
        inds=jnp.zeros((3, 3), dtype=jnp.int32),
        vals=jnp.ones((3,)), dims=(2, 2, 2), nnz=3)
    pd = tmp_path / "dup.tnsb"
    write_tnsb(pd, dup)
    with pytest.raises(ValueError, match="duplicate"):
        read_any(pd, duplicates="error")
    assert read_any(pd).nnz == 1          # "sum" collapses
    assert read_any(pd, duplicates="keep").nnz == 3


def test_cache_stale_version_self_heals(tmp_path, monkeypatch):
    import json as json_mod

    t = small_tensor()
    c = IngestCache(tmp_path / "c")
    cold = ingest(t, cache=c)
    key = cold.key
    # corrupt the entry's version on disk
    meta_path = c._dir(key) / "meta.json"
    meta = json_mod.loads(meta_path.read_text())
    meta["version"] = -1
    meta_path.write_text(json_mod.dumps(meta))
    again = ingest(t, cache=c)
    assert not again.cache_hit            # stale entry is a miss...
    third = ingest(t, cache=c)
    assert third.cache_hit                # ...and was rebuilt, not wedged


def test_cpals_rejects_conflicting_tile_with_ingested():
    t = skewed_tensor(nnz=200)
    ing = ingest(t, tile=(256, 64))
    with pytest.raises(ValueError, match="ingested with block=256"):
        cp_als(ing, rank=3, niters=1, block=512)
    # defaults follow the handle's geometry
    dec = cp_als(ing, rank=3, niters=1, key=KEY)
    assert np.isfinite(float(dec.fit))
