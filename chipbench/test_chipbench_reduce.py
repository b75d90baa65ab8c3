"""The yardstick's arithmetic: the compulsory work of an MTTKRP against the
hand numbers, and the trace reduction on a trace built by hand in the
profiler's own format, with every number worked out."""
import pytest

from chipbench import roofline, trace


@pytest.mark.parametrize("dims, nnz, gb", [
    # 3 x (nnz x 16 B + (sum of dims) x 35 x 4 B)
    ((41_000, 11_000, 75_000), 7_998_641, 0.437274768),   # yelp
    ((12_000, 9_000, 29_000), 10_000_000, 0.501),         # nell-2 (cut)
])
def test_sweep_bytes_match_the_hand_numbers(dims, nnz, gb):
    assert roofline.sweep_bytes(dims, nnz, 35) == pytest.approx(gb * 1e9)
    assert roofline.sweep_flops(dims, nnz, 35) == 3 * 3 * nnz * 35


def test_least_time_is_the_bandwidth_bound_on_v5e():
    peaks = roofline.peaks_for("TPU v5 lite")
    dims, nnz = (41_000, 11_000, 75_000), 7_998_641
    least = roofline.least_seconds(roofline.sweep_flops(dims, nnz, 35),
                                   roofline.sweep_bytes(dims, nnz, 35), peaks)
    assert least == pytest.approx(0.437274768e9 / 819e9)
    assert least == pytest.approx(0.534e-3, rel=1e-3)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks_for("cpu")


def _plane(pid, name, lines):
    """A text-format XPlane: ``lines`` maps a line name to its events,
    each ``(name, start_ns, end_ns)``."""
    names = sorted({ev[0] for evs in lines.values() for ev in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (line, evs) in enumerate(lines.items(), 1):
        out.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0')
        out += [f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                f"duration_ps: {(e - s) * 1000} }}" for n, s, e in evs]
        out.append("}")
    out += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
            for n, i in ids.items()]
    return "\n".join(out + ["}"])


def test_reduce_hand_built_trace():
    """Two devices, overlapping ops, an op across the window's start, and
    host spans that name the gaps; every number worked out by hand (ns)."""
    from jax.profiler import ProfileData

    host = _plane(1, "/host:CPU", {"python": [
        ("window", 100, 1100), ("sweep", 100, 400), ("submit", 420, 520),
        ("sweep", 500, 800), ("probe", 1150, 1250)]})
    dev0 = _plane(2, "/device:TPU:0", {
        "XLA Ops": [("early", 50, 150), ("fusion.a", 150, 350),
                    ("fusion.b", 300, 380), ("fusion.a", 550, 750),
                    ("probe_op", 1160, 1220)],
        "Steps": [("step", 100, 1100)]})
    dev1 = _plane(3, "/device:TPU:1", {"XLA Ops": [("fusion.a", 200, 300)]})
    red = trace.reduce_profile(ProfileData.from_text_proto(
        "\n".join([host, dev0, dev1])))
    assert red.devices == 2
    assert red.window_s == pytest.approx(1000e-9)
    # device 0 busy [100, 380) and [550, 750): 480; device 1: 100
    assert red.busy_s == pytest.approx(290e-9)
    assert red.idle_pct == pytest.approx(71.0)
    assert red.ops == [["fusion.a", pytest.approx(250e-9)],
                       ["fusion.b", pytest.approx(40e-9)],
                       ["early", pytest.approx(25e-9)]]
    assert red.gaps == [["sweep", pytest.approx(800e-9)],
                        ["window", pytest.approx(350e-9)],
                        ["submit", pytest.approx(170e-9)],
                        ["sweep", pytest.approx(100e-9)]]
    assert red.span_busy("probe") == [pytest.approx(30e-9)]


def test_reduce_refuses_a_trace_without_device_work():
    from jax.profiler import ProfileData

    host = _plane(1, "/host:CPU", {"python": [("window", 0, 100)]})
    idle = _plane(2, "/device:TPU:0", {"XLA Ops": [("late", 200, 300)]})
    for text in (host, "\n".join([host, idle])):
        with pytest.raises(ValueError):
            trace.reduce_profile(ProfileData.from_text_proto(text))


def test_merge_and_busy_clip():
    assert trace._merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    red = trace.Reduced(window_s=1.0, busy_s=0.0, devices=2, ops=[], gaps=[],
                        spans={"probe": [(0, 10)]},
                        intervals=[[(0, 4), (6, 20)], [(2, 3)]])
    # device 0: 4 + 4 ns inside [0, 10); device 1: 1 ns; mean over devices
    assert red.busy_in(0, 10) == pytest.approx(4.5e-9)
    assert red.span_busy("probe") == [pytest.approx(4.5e-9)]
