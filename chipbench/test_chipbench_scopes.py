"""The reduction of a traced window by program scope (``scopes.py``): path
reading, the compiled text's op map, a trace built by hand in the
profiler's own format with every number worked out, and a chip trace of
the program's own sweep (``testdata/``)."""
from pathlib import Path

import pytest

from chipbench import scopes, trace
from chipbench.test_chipbench_reduce import _plane


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_iteration_impl)/mttkrp/mode0/jit(mttkrp)/gather/gather",
     "mttkrp/mode0/gather"),
    ("jit(_iteration_impl)/mttkrp/mode2/jit(mttkrp)/kernel/mttkrp/"
     "pallas_call", "mttkrp/mode2/kernel"),
    ("jit(_iteration_impl)/mttkrp/mode1/kernel/scatter-add",
     "mttkrp/mode1/kernel"),
    ("jit(_iteration_impl)/mttkrp/mode1/jit(mttkrp)/slice", "mttkrp/mode1"),
    ("jit(_iteration_impl)/epilogue/mode1/jit(_cholesky)/cholesky",
     "epilogue/mode1"),
    ("jit(_iteration_impl)/mttkrp/mode0/gather", "mttkrp/mode0"),
    ("jit(f)/mttkrp/gather/gather", None),
    ("jit(mttkrp)/gather/gather", None),
    ("factors[1]", None),
])
def test_scope_of_reads_path_components(op_name, scope):
    """The last component is the op itself; a layer needs its mode."""
    assert scopes.scope_of(op_name) == scope


HLO = """HloModule jit__iteration_impl, is_scheduled=true

%fused_computation.1 (param_0: f32[8,4]) -> f32[8,4] {
  %param_0 = f32[8,4]{1,0} parameter(0)
  ROOT %mul.1 = f32[8,4]{1,0} multiply(%param_0, %param_0), metadata={op_name="jit(_iteration_impl)/mttkrp/mode0/jit(mttkrp)/gather/mul"}
}

ENTRY %main.5 (p: f32[8,4]) -> f32[8,4] {
  %p = f32[8,4]{1,0} parameter(0), metadata={op_name="factors[0]"}
  %fusion.1 = f32[8,4]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_iteration_impl)/mttkrp/mode0/jit(mttkrp)/gather/mul"}
  %mttkrp.3 = f32[8,4]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_iteration_impl)/mttkrp/mode0/jit(mttkrp)/kernel/mttkrp/pallas_call" stack_frame_id=2}
  %copy.2 = f32[8,4]{0,1} copy(%p), metadata={op_name="factors[0]"}
  ROOT %fusion.2 = f32[8,4]{1,0} fusion(%mttkrp.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_iteration_impl)/epilogue/mode0/div"}
}
"""


def test_hlo_paths_from_compiled_text():
    hlo = scopes.HloPaths.from_text(HLO)
    assert hlo.module == "jit__iteration_impl"
    assert scopes.scope_of(hlo.paths["fusion.1"]) == "mttkrp/mode0/gather"
    assert scopes.scope_of(hlo.paths["mttkrp.3"]) == "mttkrp/mode0/kernel"
    assert scopes.scope_of(hlo.paths["fusion.2"]) == "epilogue/mode0"
    assert hlo.paths["copy.2"] == "factors[0]"
    # a trace names an op by its whole instruction, and its program's run
    event = "%mttkrp.3 = f32[8,4]{1,0:T(8,128)} custom-call(f32[8,4] %fusion.1)"
    assert hlo.path("jit__iteration_impl", event) == hlo.paths["mttkrp.3"]
    assert hlo.path("jit__copy", event) is None


def test_reduce_by_program_scope():
    """Scoped device time in the window, worked out by hand (ns): ops of
    another program with the same names, an op across the window's end,
    and an op under no scope."""
    from jax.profiler import ProfileData

    host = _plane(1, "/host:CPU", {"python": [("window", 100, 1000)]})
    dev = _plane(2, "/device:TPU:0", {
        "XLA Modules": [("jit__iteration_impl(1)", 100, 700),
                        ("jit__copy(2)", 700, 760),
                        ("jit__iteration_impl(1)", 900, 1200)],
        "XLA Ops": [("%fusion.1 = f32[8,4] fusion()", 100, 300),
                    ("%mttkrp.3 = f32[8,4] custom-call()", 300, 450),
                    ("%copy.2 = f32[8,4] copy()", 450, 500),
                    ("%fusion.2 = f32[8,4] fusion()", 500, 540),
                    ("%fusion.1 = f32[8,4] fusion()", 700, 760),
                    ("%fusion.1 = f32[8,4] fusion()", 900, 1100)]})
    data = ProfileData.from_text_proto("\n".join([host, dev]))
    sc = scopes.reduce_scopes(data, scopes.HloPaths.from_text(HLO))
    assert sc.window_s == pytest.approx(900e-9)
    assert sc.busy_s == pytest.approx(600e-9)
    assert sc.scopes == {"epilogue/mode0": pytest.approx(40e-9),
                         "mttkrp/mode0/gather": pytest.approx(300e-9),
                         "mttkrp/mode0/kernel": pytest.approx(150e-9)}
    assert sc.scope_s("mttkrp", "gather") == pytest.approx(300e-9)
    assert sc.scope_s("mttkrp") == pytest.approx(450e-9)
    assert sc.scope_s("epilogue") == pytest.approx(40e-9)
    assert sc.scope_s("mttkrp", "other") is None
    # the copy of another program (60) and the layout copy (50) are not
    # claimed: 110 of 600
    assert sc.scoped_s == pytest.approx(490e-9)
    assert sc.unscoped_pct == pytest.approx(100 * 110 / 600)
    # the busy time is the harness's own reduction's
    assert sc.busy_s == trace.reduce_profile(data).busy_s
    # a program whose ops carry no scope reads none
    bare = scopes.HloPaths.from_text(HLO.replace("/mode0", ""))
    none = scopes.reduce_scopes(data, bare)
    assert none.scopes == {} and none.scope_s("epilogue") is None
    assert none.unscoped_pct == pytest.approx(100.0)


TESTDATA = Path(__file__).resolve().parent / "testdata"


def test_reduce_a_chip_trace_of_the_programs_sweep():
    """A TPU v5e trace of two of the program's own fused sweeps
    (``record_sweep_trace.py``: 600 x 400 x 800, 400k non-zeros, rank 35,
    plan ``pallas``): every mode's gathers, kernel and epilogue take device
    time, and the program's scopes claim all but 1 % of the window's busy
    time."""
    path = str(TESTDATA / "sweep_small.xplane.pb")
    hlo = scopes.HloPaths.from_text(
        (TESTDATA / "sweep_small.hlo.txt").read_text())
    sc = scopes.reduce_scopes_file(path, hlo)
    for n in range(3):
        for scope in (f"mttkrp/mode{n}/gather", f"mttkrp/mode{n}/kernel",
                      f"epilogue/mode{n}"):
            assert sc.scopes.get(scope, 0.0) > 0.0, scope
    assert sum(sc.scopes.values()) == pytest.approx(sc.busy_s, rel=0.01)
    assert sc.unscoped_pct < 1.0
    red = trace.reduce_trace(path)
    assert sc.busy_s == red.busy_s
    assert len(red.span_busy("sweep")) == 2
    assert len(red.span_busy("probe")) == 3
