"""Direct unit coverage for repro.dist: StragglerMonitor edge cases,
int8+error-feedback round trips on adversarial pytrees, and the shared
collectives vocabulary."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import (StragglerMonitor, axis_product, batch_axes,
                        cpals_axes, make_mesh)
from repro.dist.compress import (compress_grads_int8, compression_ratio,
                                 decompress_grads_int8, init_error_feedback)


# ---------------------------------------------------------------------------
# StragglerMonitor
# ---------------------------------------------------------------------------

def test_straggler_warmup_window_is_silent():
    """No flags until every seen host has `warmup` samples."""
    mon = StragglerMonitor(window=8, threshold=1.5, patience=1, warmup=3)
    for host in range(3):
        mon.record(host, 10.0 if host == 1 else 1.0)
    assert mon.check() == {}          # 1 sample each < warmup
    for host in range(3):
        mon.record(host, 10.0 if host == 1 else 1.0)
    assert mon.check() == {}          # 2 samples each, still warming up
    for host in range(3):
        mon.record(host, 10.0 if host == 1 else 1.0)
    assert mon.check() == {1: "persistent"}   # patience=1 escalates at once


def test_straggler_patience_escalation_and_reset():
    """A host recovering below threshold resets its patience counter."""
    mon = StragglerMonitor(window=2, threshold=1.5, patience=2, warmup=1)
    for host in (0, 1, 2):
        mon.record(host, 1.0)
    mon.record(3, 4.0)
    assert mon.check() == {3: "slow"}          # first strike
    # recovery: window=2 mean becomes (4.0 + 0.1)/2 = 2.05 ... still slow?
    # push two fast steps so the rolling mean drops under 1.5x median
    for _ in range(2):
        for host in (0, 1, 2, 3):
            mon.record(host, 1.0)
    assert mon.check() == {}                   # counter reset on recovery
    # slow again: needs `patience` consecutive strikes to escalate
    for host in (0, 1, 2):
        mon.record(host, 1.0)
    mon.record(3, 9.0)
    mon.record(3, 9.0)
    assert mon.check() == {3: "slow"}          # strike 1 (post-reset)
    assert mon.check()[3] == "persistent"      # strike 2 == patience


def test_straggler_single_host_never_flags():
    """The smoke launcher records only host 0; median == own mean."""
    mon = StragglerMonitor(window=4, threshold=1.5, patience=1, warmup=1)
    for t in (1.0, 5.0, 0.1, 3.0):
        mon.record(0, t)
        assert mon.check() == {}


def test_straggler_validates_args():
    with pytest.raises(ValueError):
        StragglerMonitor(window=0)
    with pytest.raises(ValueError):
        StragglerMonitor(threshold=1.0)
    with pytest.raises(ValueError):
        StragglerMonitor(window=2, warmup=3)   # window could never fill


def test_record_step_times_single_process():
    from repro.dist.straggler import record_step_times
    mon = StragglerMonitor(window=4, threshold=1.5, patience=1, warmup=1)
    record_step_times(mon, 0.25)
    record_step_times(mon, 0.75)
    assert mon.means() == {0: 0.5}


def test_straggler_reset_clears_history():
    mon = StragglerMonitor(window=4, threshold=1.5, patience=1, warmup=1)
    mon.record(0, 1.0)
    mon.record(1, 50.0)
    assert mon.check() != {}
    mon.reset()
    assert mon.check() == {}
    assert mon.means() == {}


# ---------------------------------------------------------------------------
# int8 + error-feedback compression
# ---------------------------------------------------------------------------

def _adversarial_tree():
    return {
        "zeros": jnp.zeros((7, 3)),                       # scale == 0 path
        "range": jnp.array([1e-8, 1.0, -1e8, 3e7]),       # huge dynamic range
        "step": jnp.array(42, dtype=jnp.int32),           # int leaf
        "nested": {"w": jnp.linspace(-2.0, 2.0, 33),
                   "mask": jnp.ones((4,), jnp.int32)},
    }


def test_int8_roundtrip_error_bound():
    """|decompressed - original| <= scale/2 = max|g| / 254 per leaf."""
    tree = _adversarial_tree()
    ef = init_error_feedback(tree)
    q, scales, new_ef = compress_grads_int8(tree, ef)
    deq = decompress_grads_int8(q, scales)
    for key in ("zeros", "range"):
        g = np.asarray(tree[key], np.float32)
        d = np.asarray(deq[key])
        bound = np.max(np.abs(g)) / 254.0 + 1e-12
        np.testing.assert_array_less(np.abs(d - g), bound + 1e-6 * np.abs(g))


def test_int8_zero_tree_is_exact():
    tree = {"a": jnp.zeros((5, 5)), "b": (jnp.zeros((3,)),)}
    q, s, ef = compress_grads_int8(tree, init_error_feedback(tree))
    deq = decompress_grads_int8(q, s)
    for leaf in jax.tree.leaves(deq):
        assert float(jnp.max(jnp.abs(leaf))) == 0.0
    for leaf in jax.tree.leaves(ef):
        assert float(jnp.max(jnp.abs(leaf))) == 0.0


def test_int8_int_leaves_pass_through():
    tree = _adversarial_tree()
    q, s, _ = compress_grads_int8(tree, init_error_feedback(tree))
    assert q["step"].dtype == jnp.int32
    assert int(q["step"]) == 42
    deq = decompress_grads_int8(q, s)
    assert deq["step"].dtype == jnp.int32          # untouched on the way back
    np.testing.assert_array_equal(np.asarray(deq["nested"]["mask"]),
                                  np.ones((4,), np.int32))


def test_int8_error_feedback_identity():
    """a = f32(g) + e decomposes exactly as q*scale + e' (float assoc.)."""
    key = jax.random.PRNGKey(7)
    g = {"w": 10.0 ** jax.random.uniform(key, (256,), minval=-6, maxval=6)}
    ef0 = {"w": jax.random.normal(jax.random.fold_in(key, 1), (256,)) * 1e-3}
    q, s, ef1 = compress_grads_int8(g, ef0)
    deq = decompress_grads_int8(q, s)
    lhs = np.asarray(g["w"], np.float32) + np.asarray(ef0["w"], np.float32)
    rhs = np.asarray(deq["w"]) + np.asarray(ef1["w"])
    np.testing.assert_allclose(lhs, rhs, rtol=1e-6)


def test_int8_error_feedback_drives_mean_error_down():
    """With EF, quantization error does not accumulate over repeated steps:
    the sum of decompressed grads tracks the sum of true grads."""
    key = jax.random.PRNGKey(3)
    true_sum = np.zeros((64,), np.float32)
    deq_sum = np.zeros((64,), np.float32)
    ef = init_error_feedback({"w": jnp.zeros((64,))})
    for i in range(50):
        g = jax.random.normal(jax.random.fold_in(key, i), (64,))
        q, s, ef = compress_grads_int8({"w": g}, ef)
        deq_sum += np.asarray(decompress_grads_int8(q, s)["w"])
        true_sum += np.asarray(g)
    # residual never exceeds one quantization step of the running scale
    assert np.max(np.abs(deq_sum - true_sum)) < 0.1


def test_int8_structure_preserved_under_jit():
    tree = {"a": jnp.ones((8, 8)), "b": (jnp.full((4,), -3.0),
                                         jnp.array(1, jnp.int32))}
    ef = init_error_feedback(tree)

    @jax.jit
    def roundtrip(t, e):
        q, s, ne = compress_grads_int8(t, e)
        return decompress_grads_int8(q, s), ne

    deq, ne = roundtrip(tree, ef)
    assert jax.tree.structure(deq) == jax.tree.structure(tree)
    assert jax.tree.structure(ne) == jax.tree.structure(tree)
    np.testing.assert_allclose(np.asarray(deq["a"]), np.ones((8, 8)),
                               rtol=1e-2)


def test_int8_mismatched_ef_raises():
    with pytest.raises(ValueError):
        compress_grads_int8({"a": jnp.ones((3,)), "b": jnp.ones((3,))},
                            {"a": jnp.zeros((3,))})


def test_compression_ratio_counts_wire_bytes():
    tree = {"w": jnp.zeros((1000,), jnp.float32)}     # 4000B -> 1004B
    r = compression_ratio(tree)
    assert 3.9 < r < 4.0
    assert compression_ratio({"i": jnp.zeros((10,), jnp.int32)}) == 1.0


# ---------------------------------------------------------------------------
# collectives vocabulary (host-side helpers; no shard_map needed)
# ---------------------------------------------------------------------------

def test_cpals_axes_single_and_multipod():
    mesh = make_mesh((1, 1), ("data", "model"))
    ax = cpals_axes(mesh)
    assert ax.row == ("data",) and ax.col == "model"
    assert ax.n_row == 1 and ax.n_col == 1 and ax.n_all == 1
    assert ax.all_axes == ("data", "model")
    # jax 0.9 normalizes a one-axis tuple entry to the bare axis name, so
    # compare specs, not their tuples
    assert ax.grid_spec() == jax.sharding.PartitionSpec(("data",), "model")
    assert axis_product(mesh, ("data", "model")) == 1
    assert axis_product(mesh, ()) == 1


def test_batch_axes_pod_rule():
    assert batch_axes() == "data"
    assert batch_axes(multi_pod=True) == ("pod", "data")


def test_cpals_axes_requires_model_axis():
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError):
        cpals_axes(mesh)
