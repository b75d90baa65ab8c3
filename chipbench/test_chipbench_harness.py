"""The harness on the CPU at a tiny size: the files ``BENCHMARK.json`` names,
a cell added by files alone, the refusal without a TPU, whole runs of each
kind of cell, and ``correct`` coming out false for the control and for each
fault the timed path can have.

The tiny cells keep their own limits, set from CPU readings at this size in
the same way as the chip's limits (``PERF.md``), on 9 seeds and 5 control
seeds: sound runs read a sweep residual of at most 1.1e-6, the control at
least 9.8e-6; served ``values_at`` at most 2.2e-7 against the control's
2.9e-3, ``top_k`` 3.5e-7 against 2.8e-6.
"""
import dataclasses
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import control, run, spec, traffic

REPO = Path(__file__).resolve().parent.parent
TINY = {"dims": [300, 200, 400], "nnz": 20000}
TINY_LIMITS = {"sweeps": {"sweep_residual": 4e-6},
               "open_loop": {"values_at_err": 2e-6, "top_k_err": 1.2e-6}}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# the serving mix and its cell, which ``BENCHMARK.json`` does not hold until
# its rate is set from a knee sweep on the chip (``PERF.md``)
SERVE_MIX = {"kind": "open_loop", "rate_per_s": 200, "tenants": 4,
             "tenant_zipf_s": 1.0, "mix": {"top_k": 0.5, "values_at": 0.5},
             "k": 10, "coords_per_values_at": 32, "index_skew": 1.5,
             "check_sample": 200, "grace_s": 60}
SERVE_ENTRIES = {
    "workloads": [{"name": "yelp.serve", "config": "yelp",
                   "traffic": "serve_open_loop", "chips": 1, "why": "tiny"}],
    "end_to_end": [{"name": n, "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock",
                    "workloads": ["yelp.serve"]}
                   for n in ("top_k_p99_ms", "values_at_p99_ms")],
    "per_layer": [{"name": n, "unit": u, "better": b,
                   "source": "device_trace", "layer": "serve",
                   "moves": "top_k_p99_ms", "workloads": ["yelp.serve"]}
                  for n, u, b in (("device_idle_pct.serve", "%", "lower"),
                                  ("serve_requests_per_batch", "req/batch",
                                   "higher"))]}
SEED = 2**31 + 12345  # more than 32 signed bits hold


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout of the benchmark's files with every configuration cut to
    a size the CPU runs in seconds."""
    root = tmp_path_factory.mktemp("bench")
    for d in ("metrics", "traffic"):
        shutil.copytree(REPO / "chipbench" / d, root / "chipbench" / d)
    (root / "chipbench" / "configs").mkdir()
    bench = spec.load_benchmark(REPO)
    for c in bench["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        cfg.update(TINY, limits=TINY_LIMITS)
        (root / c["file"]).write_text(json.dumps(cfg))
    spec.traffic_path(root, "serve_open_loop").write_text(
        json.dumps(SERVE_MIX))
    for section, entries in SERVE_ENTRIES.items():
        bench[section] += entries
    assert spec.validate(bench, root) == []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def fresh_jit():
    """Compiled programs traced with a fault must not outlive its test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _run(root, workload, seconds=1.0):
    cell = spec.resolve(workload, root)
    return run.run_cell(cell, seed=SEED, seconds=seconds, traced=False,
                        device=CPU, t_start=0.0)


def test_benchmark_resolves():
    bench = spec.load_benchmark(REPO)
    assert spec.validate(bench, REPO) == []
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"], REPO)
        assert cell.traffic["kind"] in run.RUNNERS
        assert set(cell.config["limits"][cell.traffic["kind"]])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))


@pytest.mark.parametrize("field, value", [
    ("name", "bad name"), ("name", "a,b"), ("name", "a/b"),
    ("unit", "tokens per second"), ("unit", "µs"), ("source", "guess")])
def test_validate_refuses_bad_names_and_units(field, value):
    bench = spec.load_benchmark(REPO)
    bench["end_to_end"][1][field] = value
    assert spec.validate(bench, REPO)


def test_cell_added_by_files_alone(tmp_path):
    """A configuration, a mix and a metric, each a new file and entry."""
    shutil.copytree(REPO / "chipbench" / "configs",
                    tmp_path / "chipbench" / "configs")
    shutil.copytree(REPO / "chipbench" / "traffic",
                    tmp_path / "chipbench" / "traffic")
    shutil.copytree(REPO / "chipbench" / "metrics",
                    tmp_path / "chipbench" / "metrics")
    bench = spec.load_benchmark(REPO)
    cfg = json.loads((REPO / "chipbench/configs/yelp.json").read_text())
    (tmp_path / "chipbench/configs/stub.json").write_text(json.dumps(cfg))
    spec.traffic_path(tmp_path, "stub_burst").write_text(
        json.dumps(dict(SERVE_MIX, rate_per_s=7)))
    spec.metric_path(tmp_path, "stub_metric").write_text(
        "def read(ctx):\n    return ctx.get('stub')\n")
    bench["configs"].append({"name": "stub", "source": "https://example.org",
                             "file": "chipbench/configs/stub.json",
                             "reduced": [], "why": "stub"})
    bench["workloads"].append({"name": "stub.burst", "config": "stub",
                               "traffic": "stub_burst", "chips": 1,
                               "why": "stub"})
    bench["per_layer"].append({"name": "stub_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "device", "moves": "sweep_ms",
                               "workloads": ["stub.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert spec.validate(bench, tmp_path) == []
    cell = spec.resolve("stub.burst", tmp_path)
    assert cell.traffic["rate_per_s"] == 7
    assert cell.reader("stub_metric")({"stub": 3.5}) == 3.5
    assert "stub_metric" in [m["name"] for m in cell.per_layer]
    with pytest.raises(spec.SpecError):
        spec.resolve("no.such.cell", tmp_path)


def test_refuses_without_a_tpu(capsys):
    assert jax.default_backend() != "tpu"
    rc = run.main(["--workload", "yelp.fit", "--seed", "1", "--seconds",
                   "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "needs 1 TPU" in out.err


def test_schedule_is_the_same_work_for_every_seed():
    mix = dict(SERVE_MIX, rate_per_s=400)
    dims = (41000, 11000, 75000)
    a = traffic.open_loop(mix, dims, SEED, 2.0)
    b = traffic.open_loop(mix, dims, SEED + 1, 2.0)
    again = traffic.open_loop(mix, dims, SEED, 2.0)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 2.0)
    np.testing.assert_allclose(np.sort(np.diff(a.due, prepend=0.0)),
                               np.sort(np.diff(b.due, prepend=0.0)),
                               rtol=1e-9)
    assert a.due[-1] < 2.0
    for f in ("kind", "tenant"):
        assert np.array_equal(np.bincount(getattr(a, f)),
                              np.bincount(getattr(b, f)))
    assert np.array_equal(a.coords, again.coords)
    assert not np.array_equal(a.coords, b.coords)
    shares = np.bincount(a.tenant) / len(a)
    np.testing.assert_allclose(shares, [0.48, 0.24, 0.16, 0.12], atol=0.01)


def test_fit_cell_is_correct(tiny_root):
    line = _run(tiny_root, "yelp.fit")
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"setup_s", "sweep_ms"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"


def test_serve_cell_is_correct(tiny_root):
    line = _run(tiny_root, "yelp.serve")
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"setup_s", "top_k_p99_ms",
                                    "values_at_p99_ms"}
    assert line["attempted"] == 200 and line["failed"] == 0


@pytest.mark.parametrize("workload", ["yelp.fit", "yelp.serve"])
def test_control_fails(tiny_root, workload):
    """The reference in the program's place, with three-pass bfloat16
    products: some number has to exceed its limit."""
    cell = spec.resolve(workload, tiny_root)
    limits = cell.config["limits"][cell.traffic["kind"]]
    if cell.traffic["kind"] == "sweeps":
        nums = control.fit_reading(cell, SEED, True, 0.2)
    else:
        nums = control.serve_reading(cell, SEED, True, 1.0)
    assert any(nums[k] > v for k, v in limits.items()), nums


def _unchanged(ws, factors, grams, norm_x_sq, **kw):
    return (tuple(factors), tuple(grams),
            jnp.ones((factors[0].shape[1],), factors[0].dtype),
            jnp.float32(0.5))


def _half_the_nonzeros(mttkrp):
    def fault(ws, factors, mode, **kw):
        keep = (jnp.arange(ws.vals.shape[0]) % 2 == 0) * 2.0
        return mttkrp(dataclasses.replace(ws, vals=ws.vals * keep), factors,
                      mode, **kw)
    return fault


def test_fit_faults_are_not_correct(tiny_root, monkeypatch, fresh_jit):
    import importlib

    cpals = importlib.import_module("repro.core.cpals")

    with monkeypatch.context() as m:
        m.setattr(cpals, "_iteration", _unchanged)
        assert not _run(tiny_root, "yelp.fit", 0.2)["correct"]
    jax.clear_caches()
    with monkeypatch.context() as m:
        m.setattr(cpals, "mttkrp", _half_the_nonzeros(cpals.mttkrp))
        assert not _run(tiny_root, "yelp.fit", 0.2)["correct"]


@pytest.mark.parametrize("kind", ["values_at", "top_k"])
def test_altered_answer_is_not_correct(tiny_root, monkeypatch, kind):
    from repro.serve.registry import TenantModel

    original = getattr(TenantModel, kind)

    def altered(self, *args):
        out = original(self, *args)
        if kind == "values_at":
            return out * np.float32(1.0001)
        scores, items = out
        # every slot answers the best item: a repeated item
        return scores, np.repeat(items[..., :1], items.shape[-1], axis=-1)

    monkeypatch.setattr(TenantModel, kind, altered)
    assert not _run(tiny_root, "yelp.serve")["correct"]
