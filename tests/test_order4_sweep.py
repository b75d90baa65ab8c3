"""Order-4 CP-ALS on the path the benchmark drives, at a small shape cut
like FROSTT enron's (sender x receiver x word x date): one long mode, one
short one, every mode at least the rank long.  ``chipbench.fit_cell.setup``
goes through ``Session``/``RunConfig``, ingest, plan and the sorted
workspaces; each fused sweep (``cpals._iteration``) is held against the
float64 reference (``chipbench/reference.py``) and each mode's MTTKRP
against the dense oracle.
"""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.mttkrp import mttkrp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from chipbench import fit_cell  # noqa: E402

DIMS = (30, 24, 400, 10)
NNZ = 3000
RANK = 8
SEED = 2147483017
SWEEPS = 3
# float32 round-off of a rank-8 solve reads 1.4e-7 to 5.0e-7 here; the
# reference's three-pass bfloat16 control 7.5e-6 to 1.4e-5 (chipbench/
# control.py's precision): a residual between them holds the program to
# float32
SWEEP_RESIDUAL = 2e-6
# float32 sums of up to about 600 products a row (the 10-row date mode)
# against the dense oracle's einsum, relative to the largest entry: read
# 6e-8 to 1.5e-6
MTTKRP_RTOL = 1e-5


def _setup(plan: str, dims=DIMS, seed: int = SEED) -> dict:
    cfg = dict(dims=dims, nnz=NNZ, skew=1.0, structure_seed=0, rank=RANK,
               method="cp_als", plan=plan, executor="local")
    return fit_cell.setup(cfg, {"warmup_sweeps": 1}, seed, {})


@pytest.mark.parametrize("plan", ["pallas", "segment"])
def test_enron_shaped_sweeps_match_the_float64_reference(plan):
    state = _setup(plan)
    t = state["tensor"]
    assert state["impls"] == (plan,) * 4
    assert min(t.dims) >= RANK and t.nnz > 0.99 * NNZ
    for p in state["plan"].modes:
        got = np.asarray(mttkrp(state["ws"][p.mode], state["factors"],
                                p.mode, impl=p.impl))
        want = np.asarray(mttkrp(t.program, state["factors"], p.mode,
                                 impl="dense"))
        assert np.abs(got - want).max() <= MTTKRP_RTOL * np.abs(want).max()
    for _ in range(SWEEPS):
        before = jax.device_get(fit_cell.copy_factors(state["factors"]))
        state = fit_cell.sweep(state)
        nums = fit_cell.check_sweep(t, before, state)
        assert nums["sweep_residual"] <= SWEEP_RESIDUAL, nums
        assert abs(nums["fit"] - nums["true_fit"]) <= 1e-6, nums
