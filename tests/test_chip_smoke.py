"""chip_smoke.py off the chip: it refuses the CPU, and its phases hold at a
tiny scale with the Pallas kernels interpreted."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def _tpu_plan(smoke, monkeypatch):
    """Plan as on a TPU, so the Pallas impls run (interpreted here)."""
    make = smoke.run_config

    def run_config(*args, **kwargs):
        cfg = make(*args, **kwargs)
        return dataclasses.replace(
            cfg, plan=dataclasses.replace(cfg.plan, backend="tpu"))

    monkeypatch.setattr(smoke, "run_config", run_config)


def test_one_chip_phases_hold_at_tiny_scale(smoke, monkeypatch):
    _tpu_plan(smoke, monkeypatch)
    guard = smoke.kernel_guard
    caught = []

    def interpreted_guard(sess):
        # on the CPU the kernels are interpreted: the guard must say so
        with pytest.raises(smoke.SmokeFailure, match="tpu_custom_call"):
            guard(sess)
        caught.append(True)
        return []

    monkeypatch.setattr(smoke, "kernel_guard", interpreted_guard)
    r = smoke.run_one_chip(seed=0, sweeps=3, scale=0.0005, n_values=12,
                           n_topk=6)
    assert caught == [True]
    assert "pallas" in r["setup"]["impls"]
    assert len(r["fit"]["sweep_s"]) == 3
    assert max(m["vs_f64"] for m in r["parity"]["mttkrp"]) \
        <= smoke.MTTKRP_RTOL
    assert r["serve"]["values_at"]["requests"] == 12
    assert r["serve"]["top_k"]["requests"] == 6


def test_four_chip_path_on_four_host_devices():
    code = textwrap.dedent("""
        import dataclasses, importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke", %r)
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        r = smoke.run_four_chips(seed=0, sweeps=3, scale=0.0005)
        assert len(r["memory"]) == 4
        print("FOUR OK", r["fit_abs_err"], max(r["factor_rel_frob"]))
    """ % str(ROOT / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "FOUR OK" in r.stdout
