"""Direct unit coverage for repro.dist: StragglerMonitor edge cases and
the shared collectives vocabulary."""
import jax
import pytest

from repro.dist import StragglerMonitor, axis_product, cpals_axes, make_mesh


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


def test_cpals_axes_requires_model_axis():
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError):
        cpals_axes(mesh)
