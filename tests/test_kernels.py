"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle,
across shapes / ranks / dtypes / block sizes, plus CP-ALS integration."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import random_sparse, build_csf_tiled, init_factors, cp_als, mttkrp
from repro.core.csf import CSF
from repro.kernels import mttkrp_pallas, ops, ref
from repro.kernels.linearized_pallas import mttkrp_lin_pallas_call
from repro.kernels.mttkrp_pallas import mttkrp_pallas_call
from repro.obs.metrics import scoped_registry

KEY = jax.random.PRNGKey(7)


def make_case(dims, nnz, rank, *, skew=0.0, block=128, row_tile=64, dtype=jnp.float32):
    kt, kf = jax.random.split(KEY)
    t = random_sparse(dims, nnz, kt, skew=skew)
    factors = tuple(f.astype(dtype) for f in init_factors(t.dims, rank, kf))
    csfs = [build_csf_tiled(t, m, block=block, row_tile=row_tile)
            for m in range(t.order)]
    return t, csfs, factors


# ---------------------------------------------------------------------------
# MTTKRP kernel sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims,nnz", [
    ((50, 40, 30), 600),       # small
    ((200, 13, 77), 2000),     # ragged dims
    ((64, 64, 64), 4000),      # dense-ish
    ((500, 11, 9), 900),       # long sparse mode (many empty row tiles)
])
@pytest.mark.parametrize("mode", [0, 1, 2])
def test_mttkrp_kernel_shapes(dims, nnz, mode):
    t, csfs, factors = make_case(dims, nnz, rank=8)
    got = ops.mttkrp(csfs[mode], factors)
    want = ref.mttkrp_ref(csfs[mode], factors)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, :8]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rank", [3, 8, 35, 64, 128, 150])
def test_mttkrp_kernel_rank_padding(rank):
    """R=35 is the paper's rank; sweep across / beyond the 128-lane boundary."""
    t, csfs, factors = make_case((40, 30, 20), 800, rank=rank)
    got = ops.mttkrp(csfs[0], factors)
    want = ref.mttkrp_ref(csfs[0], factors)[:, :rank]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("block,row_tile", [(64, 32), (128, 64), (256, 128), (512, 128)])
def test_mttkrp_kernel_blockings(block, row_tile):
    t, csfs, factors = make_case((100, 50, 25), 3000, rank=16,
                                 block=block, row_tile=row_tile)
    got = ops.mttkrp(csfs[0], factors)
    want = ref.mttkrp_ref(csfs[0], factors)[:, :16]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mttkrp_kernel_dtypes(dtype):
    t, csfs, factors = make_case((40, 30, 20), 700, rank=8, dtype=dtype)
    got = ops.mttkrp(csfs[0], factors)
    want = ref.mttkrp_ref(csfs[0], factors)[:, :8]
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want, dtype=np.float32),
                               rtol=tol, atol=tol)


def test_mttkrp_kernel_skewed_collisions():
    """YELP-like skew: many collisions inside a block — the one-hot matmul
    must resolve them exactly (this is the mutex-pool analogue test)."""
    t, csfs, factors = make_case((30, 20, 10), 4000, rank=8, skew=2.0)
    for mode in range(3):
        got = ops.mttkrp(csfs[mode], factors)
        want = ref.mttkrp_ref(csfs[mode], factors)[:, :8]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-4)


def test_mttkrp_kernel_order4():
    t, csfs, factors = make_case((20, 15, 12, 10), 900, rank=8)
    got = ops.mttkrp(csfs[2], factors)
    want = ref.mttkrp_ref(csfs[2], factors)[:, :8]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_mttkrp_kernel_vs_segment_impl():
    """Cross-check the kernel against the independent segment implementation
    (different layout, different padding scheme)."""
    from repro.core import build_csf
    t, csfs, factors = make_case((60, 45, 30), 2500, rank=12)
    got = ops.mttkrp(csfs[1], factors)
    want = mttkrp(build_csf(t, 1, block=64), factors, 1, impl="segment")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# every gathered operand into the kernel; a mode's blocks in ranges
# ---------------------------------------------------------------------------

def _summed_as_the_kernel_sums(csf, factors, carry=None):
    """The MTTKRP of ``csf``'s mode in jnp and numpy, as one kernel call
    over all the mode's blocks computes it: the Khatri-Rao rows
    ``vals * B * (C * D [* E])``, lane-padded, each block's rows summed into
    its tile by the kernel's ``segment_sum``, and the blocks' sums added
    into their tiles in float32, block after block, onto zeros (or onto
    ``carry``'s tile, where given).  Returns the whole padded output."""
    block, row_tile = csf.block, csf.row_tile
    gathered = [ops._pad_lanes(factors[m][csf.other_ids[:, i]])
                for i, m in enumerate(csf.other_modes)]
    others = gathered[1]
    for g in gathered[2:]:
        others = others * g
    prod = csf.vals[:, None] * gathered[0] * others
    nblocks, rp = csf.num_blocks, prod.shape[-1]
    tiles = np.asarray(csf.block_tile)
    local = csf.row_ids.reshape(nblocks, block) - csf.block_tile[:, None] * row_tile
    sums = np.asarray(jax.jit(lambda local, prod: jax.lax.map(
        lambda a: mttkrp_pallas.segment_sum(a[0], a[1], row_tile),
        (local, prod)))(local, prod.reshape(nblocks, block, rp)))
    out = (np.zeros((csf.num_row_tiles * row_tile, rp), np.float32)
           if carry is None else np.array(carry))
    for b, tile in enumerate(tiles):
        rows = slice(tile * row_tile, (tile + 1) * row_tile)
        if b > 0 and tiles[b - 1] == tile:
            out[rows] = out[rows] + sums[b]
        else:
            start = np.zeros_like(sums[b]) if carry is None else out[rows]
            out[rows] = start + sums[b]
    return out


def _straddles(csf) -> bool:
    """Whether the output tile of the last block of ``csf``'s first kernel
    call is also the tile of the second call's first block."""
    (_, end), *_ = ops._block_ranges(csf.num_blocks, csf.order)
    tiles = np.asarray(csf.block_tile)
    return bool(tiles[end - 1] == tiles[end])


@pytest.mark.parametrize("dims,nnz,mode,straddles", [
    ((40, 15, 2000, 10), 3000, 0, True),     # the boundary inside a tile
    ((40, 15, 2000, 10), 3000, 2, False),    # the boundary between tiles
    ((24, 8, 1500, 7, 6), 2500, 0, True),
    ((24, 8, 1500, 7, 6), 2500, 2, False),
])
def test_mttkrp_in_block_ranges_is_the_one_call_sum(dims, nnz, mode, straddles):
    """From order 4 on a mode's blocks go through the kernel in two calls,
    the output carried from one to the next, and every gathered operand is
    multiplied in the kernel: the result is the single call's over the
    products ``vals * B * (C * D [* E])``, to the last bit, a tile whose
    blocks straddle the two calls included."""
    t, csfs, factors = make_case(dims, nnz, rank=8, skew=1.0, block=64,
                                 row_tile=8)
    csf = csfs[mode]
    assert len(ops._block_ranges(csf.num_blocks, t.order)) == 2
    assert _straddles(csf) == straddles
    got = np.asarray(ops.mttkrp(csf, factors))
    want = _summed_as_the_kernel_sums(csf, factors)[:csf.num_rows, :8]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_order3_mttkrp_is_one_call_over_two_operands(mode):
    t, csfs, factors = make_case((50, 40, 30), 2000, rank=8, skew=1.0,
                                 block=64, row_tile=8)
    assert ops._block_ranges(csfs[mode].num_blocks, 3) == [
        (0, csfs[mode].num_blocks)]
    got = np.asarray(ops.mttkrp(csfs[mode], factors))
    want = _summed_as_the_kernel_sums(csfs[mode], factors)
    np.testing.assert_array_equal(got, want[:csfs[mode].num_rows, :8])


@pytest.mark.parametrize("num_blocks,order,ranges", [
    (10, 3, [(0, 10)]),
    (10, 4, [(0, 5), (5, 10)]),
    (11, 4, [(0, 6), (6, 11)]),
    (11, 5, [(0, 6), (6, 11)]),
    (1, 4, [(0, 1)]),
    (7, 6, [(0, 3), (3, 6), (6, 7)]),
])
def test_block_ranges(num_blocks, order, ranges):
    assert ops._block_ranges(num_blocks, order) == ranges


def test_kernel_call_adds_onto_its_carry():
    """With ``carry`` a call's visited tiles start from their carried
    values and the tiles it never visits keep them."""
    t, csfs, factors = make_case((40, 15, 2000, 10), 3000, rank=8,
                                 block=64, row_tile=8)
    csf = csfs[2]
    lo, hi = 40, 90
    rp = mttkrp_pallas.LANE
    carry = jax.random.normal(KEY, (csf.num_row_tiles * csf.row_tile, rp))
    part = dataclasses.replace(
        csf, row_ids=csf.row_ids[lo * 64:hi * 64],
        other_ids=csf.other_ids[lo * 64:hi * 64],
        vals=csf.vals[lo * 64:hi * 64], block_tile=csf.block_tile[lo:hi])
    operands = [ops._pad_lanes(factors[m][part.other_ids[:, i]])
                .reshape(hi - lo, 64, rp)
                for i, m in enumerate(part.other_modes)]
    got = mttkrp_pallas_call(
        part.row_ids.reshape(hi - lo, 1, 64),
        part.vals.reshape(hi - lo, 1, 64), operands, part.block_tile,
        num_row_tiles=csf.num_row_tiles, row_tile=csf.row_tile,
        interpret=True, carry=carry)
    want = _summed_as_the_kernel_sums(part, factors, carry=carry)
    np.testing.assert_array_equal(np.asarray(got), want)
    visited = np.zeros(csf.num_row_tiles, bool)
    visited[np.asarray(part.block_tile)] = True
    assert 0 < visited.sum() < csf.num_row_tiles
    kept = np.repeat(~visited, csf.row_tile)
    np.testing.assert_array_equal(np.asarray(got)[kept],
                                  np.asarray(carry)[kept])


# ---------------------------------------------------------------------------
# a factor too large for VMEM gathered from a table packed k rows a lane row
# ---------------------------------------------------------------------------

def _padded_bytes(factor) -> int:
    return factor.shape[0] * mttkrp_pallas.LANE * factor.dtype.itemsize


def _mttkrp_packing_above(csf, factors, table_bytes, monkeypatch):
    """``ops.mttkrp`` traced anew with ``VMEM_TABLE_BYTES`` at
    ``table_bytes``, and the gauge of how many of the mode's gathered
    operands it read from a packed table.  The jit caches are cleared
    before and after, so no other test meets this trace."""
    monkeypatch.setattr(ops, "VMEM_TABLE_BYTES", table_bytes)
    jax.clear_caches()
    try:
        with scoped_registry() as registry:
            got = np.asarray(ops.mttkrp(csf, factors))
        gauge = registry.gauge(f"mttkrp.packed_gathers.mode{csf.mode}")
        return got, gauge.value
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("dims,nnz,mode,rank,packed", [
    # order 3, both gathered operands packed: 3 and 2 rows a lane row, row
    # counts that are not multiples of 3 or 2
    ((50, 40, 301), 2000, 0, 35, (1, 2)),
    ((50, 41, 301), 2000, 2, 64, (0, 1)),
    # order 4 in two block ranges, the carry across them; the long factor
    # in the gather position (mode 0), in the khatri_rao position (mode 3)
    ((40, 15, 2000, 10), 3000, 0, 35, (2,)),
    ((40, 15, 2000, 10), 3000, 3, 35, (2,)),
    ((40, 15, 2000, 10), 3000, 3, 64, (2,)),
    ((40, 15, 2000, 10), 3000, 1, 35, (0, 2, 3)),
    # rank 65: one row fills more than half a lane row, nothing packed
    ((50, 40, 301), 2000, 0, 65, ()),
])
def test_packed_gathers_give_the_padded_gathers_bits(dims, nnz, mode, rank,
                                                     packed, monkeypatch):
    """A factor whose lane-padded table is over ``VMEM_TABLE_BYTES`` is
    gathered from a table packed ``LANE // rank`` rows a lane row, and the
    kernel moves each row's slot to lanes ``[0, R)``: the MTTKRP is the
    one over the lane-padded tables to the last bit, in the gather and the
    khatri_rao positions and across an order-4 mode's two block ranges."""
    t, csfs, factors = make_case(dims, nnz, rank=rank, skew=1.0, block=64,
                                 row_tile=8)
    csf = csfs[mode]
    if t.order > 3:
        assert len(ops._block_ranges(csf.num_blocks, t.order)) == 2
    want, none = _mttkrp_packing_above(csf, factors, ops.VMEM_TABLE_BYTES,
                                       monkeypatch)
    assert none == 0
    # a table just under the smallest packed factor's: those pack, the
    # rest keep the padded table
    unpacked = [_padded_bytes(factors[m]) for m in csf.other_modes
                if m not in packed]
    limit = min([_padded_bytes(factors[m]) for m in packed] or [0]) - 1
    assert all(b <= limit for b in unpacked) or not packed
    got, count = _mttkrp_packing_above(csf, factors, max(limit, 0),
                                       monkeypatch)
    assert count == len(packed)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        want, _summed_as_the_kernel_sums(csf, factors)[:csf.num_rows, :rank])


@pytest.mark.parametrize("rows,rank,k", [(301, 35, 3), (300, 35, 3),
                                         (7, 64, 2), (1, 42, 3)])
def test_packed_table_holds_row_w_in_slot_w_mod_k(rows, rank, k):
    factor = jax.random.normal(KEY, (rows, rank))
    table = np.asarray(ops._packed(factor, k))
    assert table.shape == (-(-rows // k), mttkrp_pallas.LANE)
    for w in range(rows):
        lanes = slice(w % k * rank, w % k * rank + rank)
        np.testing.assert_array_equal(table[w // k, lanes],
                                      np.asarray(factor[w]))
    in_slots = np.zeros_like(table, bool)
    for w in range(rows):
        in_slots[w // k, w % k * rank:w % k * rank + rank] = True
    assert not table[~in_slots].any()


CONFIGS = Path(__file__).resolve().parent.parent / "chipbench" / "configs"


@pytest.mark.parametrize("config,counts", [("yelp", [0, 0, 0]),
                                           ("nell-2", [0, 0, 0]),
                                           ("enron", [1, 1, 0, 1])])
def test_packed_gathers_gauge_at_the_benchmark_shapes(config, counts):
    """Traced at a benchmark configuration's dims and rank, each mode sets
    ``mttkrp.packed_gathers.mode{n}`` to how many of its gathered operands
    come from a packed table: only enron's 244,268-row word factor is
    over the limit, and every mode but its own gathers it."""
    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    dims, rank, block = tuple(cfg["dims"]), cfg["rank"], 512
    factors = [jax.ShapeDtypeStruct((d, rank), jnp.float32) for d in dims]
    got = []
    for mode in range(len(dims)):
        nnz = 4 * block
        csf = CSF(mode=mode,
                  row_ids=jax.ShapeDtypeStruct((nnz,), jnp.int32),
                  other_ids=jax.ShapeDtypeStruct((nnz, len(dims) - 1),
                                                 jnp.int32),
                  vals=jax.ShapeDtypeStruct((nnz,), jnp.float32),
                  block_tile=jax.ShapeDtypeStruct((4,), jnp.int32),
                  dims=dims, nnz=nnz, block=block, row_tile=128)
        jax.clear_caches()
        with scoped_registry() as registry:
            jax.eval_shape(ops.mttkrp, csf, factors)
        got.append(registry.gauge(f"mttkrp.packed_gathers.mode{mode}").value)
    jax.clear_caches()
    assert got == counts


@pytest.mark.parametrize("rows,rank,k", [
    (100, 35, 1),         # a padded table under the limit
    (140_000, 35, 3),     # 71.7 MB padded
    (140_000, 64, 2),
    (140_000, 65, 1),     # two rows do not fit a lane row
    (140_000, 128, 1),
])
def test_slots_per_row_from_padded_bytes_and_rank(rows, rank, k):
    factor = jax.ShapeDtypeStruct((rows, rank), jnp.float32)
    assert ops._slots_per_row(factor) == k


# ---------------------------------------------------------------------------
# the one-hot contraction: three bfloat16 passes, as exact as HIGHEST
# ---------------------------------------------------------------------------

LIN_OFFSET, LIN_WIDTH = 28, 9  # a row field that straddles the two words


def _colliding_blocks(nblocks=6, block=256, row_tile=64, rp=128):
    """Two blocks a tile, each block's rows drawn from 8 of the tile's, and
    values spanning 2**-20 to 2**20; the float64 segment sum of their
    Khatri-Rao products is the answer."""
    rng = np.random.default_rng(14)
    tiles = np.repeat(np.arange(nblocks // 2), 2).astype(np.int32)
    local = rng.integers(0, 8, size=(nblocks, block)) * 7 % row_tile
    rows = (tiles[:, None] * row_tile + local).astype(np.int32)
    vals = (rng.choice([-1.0, 1.0], size=(nblocks, block))
            * 2.0 ** rng.uniform(-20, 20, size=(nblocks, block))
            ).astype(np.float32)
    b = rng.standard_normal((nblocks, block, rp)).astype(np.float32)
    c = rng.standard_normal((nblocks, block, rp)).astype(np.float32)
    want = np.zeros((tiles[-1] * row_tile + row_tile, rp))
    np.add.at(want, rows.ravel(),
              (vals.astype(np.float64)[..., None] * b * c).reshape(-1, rp))
    return rows, vals, b, c, tiles, row_tile, want


def _run_kernel(kernel, rows, vals, b, c, tiles, row_tile, interpret=True):
    rows, vals = rows[:, None], vals[:, None]
    num_row_tiles = int(tiles[-1]) + 1
    if kernel == "csf":
        return mttkrp_pallas_call(rows, vals, (b, c), tiles,
                                  num_row_tiles=num_row_tiles,
                                  row_tile=row_tile, interpret=interpret)
    # pack the row into a 64-bit index whose other bits are noise
    noise = np.random.default_rng(1).integers(0, 2**63, size=rows.shape,
                                              dtype=np.uint64)
    field = np.uint64((1 << LIN_WIDTH) - 1) << np.uint64(LIN_OFFSET)
    packed = ((noise & ~field)
              | (rows.astype(np.uint64) << np.uint64(LIN_OFFSET)))
    hi = (packed >> np.uint64(32)).astype(np.uint32)
    lo = (packed & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return mttkrp_lin_pallas_call(hi, lo, vals, b, c, tiles,
                                  num_row_tiles=num_row_tiles,
                                  row_tile=row_tile, offset=LIN_OFFSET,
                                  width=LIN_WIDTH, interpret=interpret)


@pytest.mark.parametrize("kernel", ["csf", "lin"])
def test_segment_sum_matches_float64(kernel, monkeypatch):
    """The three bfloat16 passes keep the products' 24-bit significands:
    colliding rows over 40 binades match a float64 segment sum to float32
    accuracy.  Dropping the low pass, or the middle and low, misses it: the
    tolerance tells three passes from fewer."""
    *args, want = _colliding_blocks()

    def rel_err():
        got = np.asarray(_run_kernel(kernel, *args), dtype=np.float64)
        return np.linalg.norm(got - want) / np.linalg.norm(want)

    assert rel_err() <= 2e-6
    split = mttkrp_pallas._bf16_parts

    def drop_passes(x, kept):
        parts = split(x)
        return parts[:kept] + tuple(jnp.zeros_like(p) for p in parts[kept:])

    for kept in (1, 2):
        monkeypatch.setattr(mttkrp_pallas, "_bf16_parts",
                            lambda x, kept=kept: drop_passes(x, kept))
        assert rel_err() > 2e-6, kept


def test_bf16_parts_sum_exactly():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(4096)
                    * 2.0 ** rng.uniform(-30, 30, 4096), dtype=jnp.float32)
    parts = mttkrp_pallas._bf16_parts(x)
    assert [p.dtype for p in parts] == [jnp.bfloat16] * 3
    total = sum(np.asarray(p, dtype=np.float64) for p in parts)
    np.testing.assert_array_equal(total, np.asarray(x, dtype=np.float64))


def _dot_generals(jaxpr):
    """Every dot_general in ``jaxpr`` and the jaxprs nested in its params."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)  # a ClosedJaxpr's Jaxpr
            if hasattr(inner, "eqns"):
                found += _dot_generals(inner)
    return found


@pytest.mark.parametrize("block", [64, 512])
@pytest.mark.parametrize("kernel", ["csf", "lin"])
def test_kernel_body_contracts_in_three_bf16_passes(kernel, block):
    """The kernel body holds three single-pass bfloat16 matmuls for each
    128 non-zeros of the block, accumulated in float32, and no HIGHEST
    (six-pass) float32 matmul."""
    *args, _ = _colliding_blocks(nblocks=2, block=block)
    traced = jax.make_jaxpr(
        lambda b, c: _run_kernel(kernel, args[0], args[1], b, c, args[4],
                                 args[5], interpret=False))(args[2], args[3])
    dots = _dot_generals(traced.jaxpr)
    assert len(dots) == 3 * -(-block // mttkrp_pallas.LANE)
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert eqn.params["precision"] in (
            None, (jax.lax.Precision.DEFAULT,) * 2)


# ---------------------------------------------------------------------------
# syrk kernel sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,rank", [(100, 8), (512, 35), (1000, 64),
                                       (4096, 128), (333, 150)])
def test_syrk_kernel_shapes(rows, rank):
    a = jax.random.normal(KEY, (rows, rank), dtype=jnp.float32)
    got = ops.syrk(a, blk=256)
    want = ref.syrk_ref(a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_syrk_kernel_dtypes(dtype):
    a = (jax.random.normal(KEY, (300, 40)) * 0.1).astype(dtype)
    got = ops.syrk(a)
    want = ref.syrk_ref(a)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want, dtype=np.float32), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# end-to-end: CP-ALS with the pallas MTTKRP matches the segment impl
# ---------------------------------------------------------------------------

def test_cpals_pallas_impl_matches_segment():
    t = random_sparse((30, 25, 20), 1500, KEY)
    d_seg = cp_als(t, rank=5, niters=5, impl="segment", key=KEY)
    d_pal = cp_als(t, rank=5, niters=5, impl="pallas", key=KEY,
                   block=128, row_tile=64)
    np.testing.assert_allclose(float(d_pal.fit), float(d_seg.fit), atol=1e-4)
    for a, b in zip(d_pal.factors, d_seg.factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-2)
