"""Checkpoint manager: roundtrip, atomicity, keep-k, async, decomposition
restart-resume, straggler monitor."""
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, save_pytree, load_pytree
from repro.dist import StragglerMonitor

KEY = jax.random.PRNGKey(0)


def tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32),
                  "d": (jnp.zeros((2, 2)), jnp.full((3,), 2.5))}}


def test_roundtrip(tmp_path):
    t = tree()
    save_pytree(tmp_path / "ck", t, extra={"step": 7})
    restored, extra = load_pytree(tmp_path / "ck", like=t)
    assert extra["step"] == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomic_rename_never_leaves_partial(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=False)
    mgr.save(1, tree())
    # simulate a crashed write: stale .tmp next to a good checkpoint
    (tmp_path / "step_00000002.tmp").mkdir()
    (tmp_path / "step_00000002.tmp" / "garbage").write_text("x")
    assert mgr.latest_step() == 1
    restored, extra = mgr.restore(tree())
    assert extra["step"] == 1


def test_incomplete_checkpoint_is_skipped(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=False)
    mgr.save(1, tree())
    mgr.save(2, tree())
    # corrupt the newest: mark incomplete
    meta = tmp_path / "step_00000002" / "meta.json"
    m = json.loads(meta.read_text())
    m["complete"] = False
    meta.write_text(json.dumps(m))
    assert mgr.latest_step() == 1


def test_keep_k_garbage_collection(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_save=False)
    for s in (1, 2, 3, 4, 5):
        mgr.save(s, tree())
    assert mgr.steps() == [4, 5]


def test_async_save_then_restore(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=True)
    t = tree()
    mgr.save(3, t)
    mgr.wait()
    restored, extra = mgr.restore(t)
    assert extra["step"] == 3


# ---------------------------------------------------------------------------
# decomposition resume: DecompState / CPALSState through the manager
# ---------------------------------------------------------------------------

def lowrank_tensor():
    from conftest import exact_lowrank_tensor
    return exact_lowrank_tensor((10, 9, 8), 3, KEY)


@pytest.mark.parametrize("method", ["cp_als", "cp_nn_hals", "tucker_hooi",
                                    "cp_als_streaming"])
def test_decomp_state_roundtrip_resumes_bit_exactly(tmp_path, method):
    """DecompState survives a save/load through checkpoint.manager and
    fit(..., state=restored) continues BIT-EXACTLY: the resumed run's final
    factors equal the uninterrupted run's."""
    from repro.methods import DecompState, fit, get_method

    t = lowrank_tensor()
    rank = (3, 3, 3) if method == "tucker_hooi" else 4
    kw = {"n_chunks": 3} if get_method(method).supports_streaming else {}

    states = []
    full = fit(t, rank, method=method, niters=8, key=KEY,
               checkpoint_cb=states.append, **kw)
    mid = states[3]  # the shared protocol state after iteration 4
    assert isinstance(mid, DecompState) and int(mid.iteration) == 4

    # through the manager: host npz + atomic rename + restore into the
    # pytree structure
    mgr = CheckpointManager(tmp_path / method, async_save=False)
    mgr.save(int(mid.iteration), mid)
    restored, extra = mgr.restore(mid)
    assert extra["step"] == 4
    assert isinstance(restored, DecompState)

    resumed = fit(t, rank, method=method, niters=8, key=KEY, state=restored,
                  **kw)
    for a, b in zip(full.factors, resumed.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(full.fit),
                                  np.asarray(resumed.fit))


def test_cpals_state_roundtrip_through_manager(tmp_path):
    """The historical CPALSState pytree also round-trips through the manager
    and resumes the core driver exactly (back-compat contract)."""
    from repro.core import cp_als
    from repro.core.cpals import CPALSState

    t = lowrank_tensor()
    states = []
    full = cp_als(t, rank=4, niters=6, key=KEY, checkpoint_cb=states.append)
    mid = states[2]
    assert isinstance(mid, CPALSState)

    mgr = CheckpointManager(tmp_path, async_save=False)
    mgr.save(int(mid.iteration), mid)
    restored, _ = mgr.restore(mid)
    resumed = cp_als(t, rank=4, niters=6, key=KEY, state=restored)
    for a, b in zip(full.factors, resumed.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# straggler
# ---------------------------------------------------------------------------

def test_straggler_monitor_flags_slow_host():
    mon = StragglerMonitor(window=10, threshold=1.5, patience=2)
    for step in range(8):
        for host in range(4):
            mon.record(host, 1.0 if host != 2 else 3.0)
        flags = mon.check()
    assert 2 in flags and flags[2] == "persistent"
    assert all(h == 2 for h in flags)
