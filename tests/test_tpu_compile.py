"""Compile the Pallas kernels for a described TPU v5e at yelp's geometry.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip that
is described and not attached, and refuses what the chip would refuse (block
shapes the Mosaic lowering cannot tile, programs larger than HBM).  Interpret
mode accepts all of that, so these tests are what keeps the kernels
compilable between chip runs.

yelp at its published scale (``PAPER_DATASETS``: 8.0M non-zeros) sorts into
at most 15,902 blocks of 512 per mode; the paper's rank 35 pads to 128 lanes.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.csf import CSF
from repro.kernels import ops
from repro.kernels.linearized_pallas import mttkrp_lin_pallas_call
from repro.kernels.mttkrp_pallas import LANE, mttkrp_pallas_call
from repro.utils.roofline import peaks_for

DIMS = (41_000, 11_000, 75_000)
NNZ = 7_998_641
BLOCKS = (15_779, 15_666, 15_902)  # per mode, build_csf at seed 0 on the CPU
BLOCK, ROW_TILE, RANK = 512, 128, 35


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _csf(sharding, mode: int, dims=DIMS, nnz=NNZ, blocks=BLOCKS) -> CSF:
    pnnz = blocks[mode] * BLOCK
    return CSF(mode=mode,
               row_ids=_sds(sharding, (pnnz,), jnp.int32),
               other_ids=_sds(sharding, (pnnz, len(dims) - 1), jnp.int32),
               vals=_sds(sharding, (pnnz,), jnp.float32),
               block_tile=_sds(sharding, (blocks[mode],), jnp.int32),
               dims=dims, nnz=nnz, block=BLOCK, row_tile=ROW_TILE)


def _kernel_operands(sharding, width: int, index_dtype=jnp.int32,
                     block: int = BLOCK):
    nb = -(-max(BLOCKS) * BLOCK // block)  # yelp's largest mode, any block
    return (_sds(sharding, (nb, 1, block), index_dtype),
            _sds(sharding, (nb, block, width), jnp.float32),
            _sds(sharding, (nb,), jnp.int32))


# the blockings the CPU tests run: the bfloat16 one-hot is tiled in 16
# sublanes, the float32 operands in 8
BLOCKINGS = [(64, 32), (128, 64), (256, 128), (BLOCK, ROW_TILE)]


@pytest.mark.parametrize("block,row_tile", BLOCKINGS)
def test_mttkrp_kernel_compiles(one_chip, block, row_tile):
    rows, rows_f, tiles = _kernel_operands(one_chip, LANE, block=block)
    vals = _sds(one_chip, rows.shape, jnp.float32)
    call = jax.jit(lambda rows, vals, b, c, tiles: mttkrp_pallas_call(
        rows, vals, (b, c), tiles, num_row_tiles=-(-DIMS[2] // row_tile),
        row_tile=row_tile, interpret=False))
    hlo = call.lower(rows, vals, rows_f, rows_f, tiles).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("block,row_tile", BLOCKINGS)
def test_linearized_kernel_compiles(one_chip, block, row_tile):
    hi, rows_f, tiles = _kernel_operands(one_chip, LANE, jnp.uint32,
                                         block=block)
    vals = _sds(one_chip, hi.shape, jnp.float32)
    call = jax.jit(lambda *a: mttkrp_lin_pallas_call(
        *a, num_row_tiles=-(-DIMS[2] // row_tile), row_tile=row_tile,
        offset=0, width=17, interpret=False))
    hlo = call.lower(hi, hi, vals, rows_f, rows_f,
                     tiles).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_ttmc_compiles_at_kronecker_width(one_chip):
    # Tucker ranks (8, 8, 8): each mode's Kronecker width is 8 x 8 = 64
    factors = tuple(_sds(one_chip, (d, 8), jnp.float32) for d in DIMS)
    compiled = jax.jit(ops.ttmc, static_argnames=("interpret",)).lower(
        _csf(one_chip, 0), factors, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_mttkrp_fits_one_chip(one_chip, topo, mode):
    """The whole jitted per-mode MTTKRP at rank 35: the gathers, the kernel
    and the slicing, within one chip's HBM."""
    factors = tuple(_sds(one_chip, (d, RANK), jnp.float32) for d in DIMS)
    compiled = jax.jit(ops.mttkrp, static_argnames=("interpret",)).lower(
        _csf(one_chip, mode), factors, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    hbm = peaks_for(topo.devices[0].device_kind).hbm_bytes
    # two lane-padded gathered operands of nnz x 128 f32 dominate: 8.3 GB
    assert total < 0.6 * hbm, (total, hbm)


# The fused sweep at the benchmark's shapes: yelp's, and FROSTT enron's dims
# at 8,000,000 drawn non-zeros (``chipbench/configs/enron.json``), sorted
# into the blocks per mode of the harness's draw on the CPU; and the most
# the whole program may take: at yelp's shape a little over its rehearsed
# 8.75 GB, at enron's three quarters of one chip's 16 GB.
ENRON_DIMS = (6_066, 5_699, 244_268, 1_176)
SWEEPS = {
    "yelp": (DIMS, NNZ, BLOCKS, 8.84e9),
    "enron": (ENRON_DIMS, 8_000_000, (15_651, 15_647, 16_613, 15_630),
              12.0e9),
}


@pytest.fixture(scope="module")
def fused_sweep(one_chip):
    """The whole fused ALS sweep at rank 35 for a shape of ``SWEEPS``, as
    the benchmark's window runs it (``cpals._iteration``, factor and gram
    buffers donated), compiled once for the module."""
    from repro.core import cpals

    compiled = {}

    def compile_sweep(shape):
        if shape not in compiled:
            dims, nnz, blocks, _ = SWEEPS[shape]
            ws = [_csf(one_chip, m, dims, nnz, blocks)
                  for m in range(len(dims))]
            factors = tuple(_sds(one_chip, (d, RANK), jnp.float32)
                            for d in dims)
            grams = tuple(_sds(one_chip, (RANK, RANK), jnp.float32)
                          for _ in dims)
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(ops, "default_interpret", lambda: False)
                jax.clear_caches()  # trace the kernels' jits anew, compiled
                compiled[shape] = cpals._iteration_jit(True).lower(
                    ws, factors, grams, _sds(one_chip, (), jnp.float32),
                    impls=("pallas",) * len(dims), norm_kind="2",
                    with_fit=True).compile()
                jax.clear_caches()
        return compiled[shape]

    return compile_sweep


@pytest.mark.parametrize("shape", sorted(SWEEPS))
def test_fused_sweep_fits_one_chip(fused_sweep, shape):
    """The whole fused ALS sweep within its share of one chip's HBM."""
    m = fused_sweep(shape).memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= SWEEPS[shape][3], (shape, total)


_GATHERED = re.compile(rf"f32\[\d+,{BLOCK},{LANE}\]")


@pytest.mark.parametrize("shape,calls,operands", [("yelp", 1, 2),
                                                   ("enron", 2, 3)])
def test_fused_sweep_multiplies_every_operand_in_the_kernel(
        fused_sweep, shape, calls, operands):
    """Each mode's MTTKRP is ``calls`` kernel calls, each streaming in one
    gathered operand per other mode, and no multiply over a non-zero
    stream (an array with more rows than the longest factor) is left
    outside them: order 4 forms the whole Khatri-Rao product in the
    kernel, in two ranges of the mode's blocks."""
    dims = SWEEPS[shape][0]
    text = fused_sweep(shape).as_text()
    per_mode = {}
    for line in text.splitlines():
        if "tpu_custom_call" in line and " custom-call(" in line:
            mode = re.search(r"/mttkrp/mode(\d+)/", line).group(1)
            layouts = re.search(r"operand_layout_constraints=\{(.*?)\}\}",
                                line).group(1)
            per_mode.setdefault(int(mode), []).append(
                len(_GATHERED.findall(layouts)))
    assert per_mode == {m: [operands] * calls for m in range(len(dims))}
    for shape_text in re.findall(r"= f32\[([\d,]+)\]\S* multiply\(", text):
        lead = [int(d) for d in shape_text.split(",")[:-1]]
        assert math.prod(lead) <= max(dims), shape_text


_DEF = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (\w+\[[\d,]*\]\{[^}]*\})")
_GATHER = re.compile(r"^\s*%[\w.\-]+ = f32\[\d+,128\]\S* fusion\(%([\w.\-]+),"
                     r".*op_name=\"([^\"]*/gather)\"")


def _gather_tables(text: str) -> list[tuple[str, str]]:
    """Each gather fusion of a compiled program: its ``op_name`` and the
    shape, layout and memory space of the table it reads (``S(1)`` is
    VMEM)."""
    defs = {}
    for line in text.splitlines():
        m = _DEF.match(line)
        if m:
            defs.setdefault(m.group(1), m.group(2))
    return [(m.group(2), defs[m.group(1)])
            for m in map(_GATHER.match, text.splitlines()) if m]


@pytest.mark.parametrize("shape,gathers,packed", [("yelp", 6, 0),
                                                  ("enron", 24, 6)])
def test_fused_sweep_gathers_an_oversized_factor_from_a_packed_table(
        fused_sweep, shape, gathers, packed):
    """At enron's shape the 244,268-row word factor, whose lane-padded table
    (125 MB) is over ``ops.VMEM_TABLE_BYTES``, is gathered in modes 0, 1 and
    3 (two block ranges each) from a table of three rank-35 rows a lane
    row, ``f32[81423,128]``, which XLA places in VMEM for at least one of
    them, and no gather reads its padded table.  At yelp's shape every
    padded table is under the limit: nothing is packed, and the longest
    factor's rows are gathered from its padded table."""
    dims = SWEEPS[shape][0]
    tables = _gather_tables(fused_sweep(shape).as_text())
    assert len(tables) == gathers
    read = [table for op, table in tables if "/packed/" in op]
    assert len(read) == packed
    rows = -(-max(dims) // (LANE // RANK))
    assert all(table.startswith(f"f32[{rows},128]") for table in read)
    assert any("S(1)" in table for table in read) == bool(packed)
    padded = f"f32[{max(dims)},128]"
    assert any(t.startswith(padded) for _, t in tables) == (not packed)


def test_kernel_keeps_its_name_under_its_scope(one_chip):
    """The scope that names the MTTKRP's reduction (``.../kernel/...``)
    leaves the custom call named after the kernel, as a trace and a
    profile's op table show it."""
    blocks = 4
    csf = CSF(mode=0, row_ids=_sds(one_chip, (blocks * BLOCK,), jnp.int32),
              other_ids=_sds(one_chip, (blocks * BLOCK, 2), jnp.int32),
              vals=_sds(one_chip, (blocks * BLOCK,), jnp.float32),
              block_tile=_sds(one_chip, (blocks,), jnp.int32),
              dims=(256, 300, 400), nnz=blocks * BLOCK, block=BLOCK,
              row_tile=ROW_TILE)
    factors = tuple(_sds(one_chip, (d, RANK), jnp.float32)
                    for d in csf.dims)
    text = jax.jit(ops.mttkrp, static_argnames=("interpret",)).lower(
        csf, factors, interpret=False).compile().as_text()
    (call,) = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    assert call.lstrip().startswith("%mttkrp")
    assert "/kernel/mttkrp/pallas_call" in call
