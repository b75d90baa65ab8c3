"""repro.obs: span tracing, the metrics registry, and their wiring into
ingest -> plan -> fit -> serve.

The two contracts worth pinning hard:

* **Zero tracer traffic when disabled** — a fit with no active tracer
  must make zero ``Tracer.span`` / ``Tracer._record`` calls (counting
  monkeypatch, same technique as test_autotune's measure counter).  The
  module-level ``span()`` fast path never touches the class.
* **Chrome-trace schema round-trip** — ``export_jsonl`` output parses
  back via ``read_trace`` and every complete event carries the
  ``ph/ts/dur/pid/tid/args`` fields chrome://tracing needs.
"""
import json
import threading

import jax
import pytest

from conftest import exact_lowrank_tensor
from repro.api import ConfigError, MethodConfig, ObsConfig, RunConfig, Session
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry, Tracer,
                       current_tracer, get_registry, read_trace,
                       scoped_registry, span, tracing)
from repro.obs.report import routine_breakdown, trace_report
from repro.obs.trace import METRICS_FILENAME, TRACE_FILENAME

KEY = jax.random.PRNGKey(0)


def lowrank():
    return exact_lowrank_tensor((10, 9, 8), 3, KEY)


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------

def test_span_nesting_parent_links():
    tracer = Tracer(xla_annotations=False)
    with tracer.activate():
        with span("outer"):
            with span("inner", mode=1):
                pass
        with span("sibling"):
            pass
    events = {e["name"]: e for e in tracer.events()}
    assert set(events) == {"outer", "inner", "sibling"}
    assert events["inner"]["args"]["parent"] == events["outer"]["args"]["id"]
    assert "parent" not in events["outer"]["args"]  # a root
    assert "parent" not in events["sibling"]["args"]
    assert events["inner"]["args"]["mode"] == 1
    # children close before parents, so ts/dur containment holds too
    assert events["inner"]["ts"] >= events["outer"]["ts"]
    assert events["inner"]["dur"] <= events["outer"]["dur"]


def test_no_active_tracer_is_inert():
    assert current_tracer() is None
    assert not tracing()
    with span("anything"):  # no tracer: shared null span, records nowhere
        pass


def test_disabled_tracer_records_nothing():
    tracer = Tracer(enabled=False)
    with tracer.activate():
        assert not tracing()
        with tracer.span("x"):
            with span("y"):
                pass
    assert tracer.events() == []


def test_sample_rate_drops_whole_subtrees():
    tracer = Tracer(sample_rate=0.5, xla_annotations=False)
    with tracer.activate():
        for i in range(4):
            with span(f"root{i}"):
                with span("child"):
                    pass
    names = [e["name"] for e in tracer.events()]
    # stride 2: roots 0 and 2 kept WITH their children, 1 and 3 dropped
    # with theirs (no orphan children in the viewer)
    assert sorted(names) == ["child", "child", "root0", "root2"]


def test_tracer_validation():
    with pytest.raises(ValueError, match="sample_rate"):
        Tracer(sample_rate=0.0)
    with pytest.raises(ValueError, match="sample_rate"):
        Tracer(sample_rate=1.5)
    with pytest.raises(ValueError, match="routines"):
        Tracer(routines="both")


def test_traced_decorator():
    from repro.obs import traced

    tracer = Tracer(xla_annotations=False)

    @traced("work.step", kind="unit-test")
    def step(x):
        return x + 1

    with tracer.activate():
        assert step(1) == 2
    (e,) = tracer.events()
    assert e["name"] == "work.step"
    assert e["args"]["kind"] == "unit-test"
    assert step(1) == 2  # and inert again outside the activation


def test_thread_isolation():
    tracer = Tracer(xla_annotations=False)
    # both workers stay alive until both have recorded: the OS may reuse
    # an exited thread's ident, and the tids must then still differ
    both = threading.Barrier(2, timeout=10)

    def worker(i):
        with tracer.activate():  # threads start with a fresh context
            with tracer.span(f"root-t{i}"):
                with tracer.span("child"):
                    pass
        both.wait()

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    events = tracer.events()
    assert len(events) == 4
    roots = {e["name"]: e for e in events if e["name"].startswith("root")}
    children = [e for e in events if e["name"] == "child"]
    assert len(roots) == 2 and len(children) == 2
    # each child links to ITS thread's root, and the tids agree
    for child in children:
        root = next(r for r in roots.values()
                    if r["args"]["id"] == child["args"]["parent"])
        assert child["tid"] == root["tid"]
    assert len({r["tid"] for r in roots.values()}) == 2


def test_export_jsonl_chrome_schema_roundtrip(tmp_path):
    tracer = Tracer(xla_annotations=False)
    with tracer.activate():
        with span("mttkrp", mode=0, impl="segment"):
            pass
    path = tracer.export_jsonl(tmp_path / "t" / TRACE_FILENAME)
    lines = path.read_text().splitlines()
    first = json.loads(lines[0])
    assert first["ph"] == "M" and first["name"] == "process_name"
    events = read_trace(path)
    assert [e["ph"] for e in events] == ["M", "X"]
    x = events[1]
    for field in ("name", "cat", "ts", "dur", "pid", "tid", "args"):
        assert field in x
    assert x["name"] == "mttkrp" and x["args"]["impl"] == "segment"
    assert x["dur"] >= 0 and x["ts"] >= 0  # microseconds since epoch


def test_read_trace_skips_corrupt_lines(tmp_path):
    p = tmp_path / TRACE_FILENAME
    p.write_text('{"ph": "X", "name": "ok", "ts": 0, "dur": 1}\n'
                 "{not json}\n"
                 '["not", "a", "dict"]\n'
                 '{"no_ph": true}\n')
    events = read_trace(p)
    assert [e["name"] for e in events] == ["ok"]


def test_clear_resets_events_and_epoch():
    tracer = Tracer(xla_annotations=False)
    with tracer.activate(), span("a"):
        pass
    assert len(tracer.events()) == 1
    tracer.clear()
    assert tracer.events() == []
    with tracer.activate(), span("b"):
        pass
    (e,) = tracer.events()
    assert e["ts"] < 1e6  # fresh epoch: ts restarts near zero


def test_span_lands_on_the_xla_trace_clock(tmp_path):
    """A span opens a profiler annotation: in an XLA trace it lies on a
    host plane under its own name and holds the ops of the jitted call
    made inside it, so a gap in the device's work can be put down to what
    the program's host code was doing then."""
    import glob

    import jax.numpy as jnp
    from jax.profiler import ProfileData

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    step(x).block_until_ready()  # compiled before the trace
    tracer = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with tracer.activate(), span("obs-bridge"):
            step(x).block_until_ready()
    assert [e["name"] for e in tracer.events()] == ["obs-bridge"]

    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "obs-bridge":
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif dict(ev.stats).get("hlo_module") == "jit_step":
                    ops.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    (span_iv,) = spans
    assert ops
    assert all(span_iv[0] <= s and e <= span_iv[1] for s, e in ops)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    c = Counter()
    assert c.inc() == 1.0 and c.inc(2.5) == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge()
    g.set(7)
    assert g.value == 7.0


def test_histogram_percentiles():
    h = Histogram()
    for v in range(1, 101):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1 and s["max"] == 100
    assert s["mean"] == pytest.approx(50.5)
    assert s["p50"] == 50 and s["p90"] == 90 and s["p99"] == 99
    assert h.percentile(100) == 100
    assert Histogram().summary()["p50"] is None


def test_histogram_window_keeps_exact_totals():
    h = Histogram(window=4)
    for v in (1, 2, 3, 4, 100, 100, 100, 100):
        h.observe(v)
    # percentiles see only the retained window...
    assert h.percentile(50) == 100
    # ...but count/total/min/max stay exact over everything observed
    s = h.summary()
    assert s["count"] == 8 and s["min"] == 1 and s["max"] == 100


def test_registry_type_conflict_raises():
    r = MetricsRegistry()
    r.counter("x").inc()
    with pytest.raises(TypeError, match="asked for Gauge"):
        r.gauge("x")


def test_registry_snapshot_and_scoping():
    with scoped_registry() as r:
        assert get_registry() is r
        r.counter("a").inc(2)
        r.gauge("b").set(1.5)
        r.histogram("c").observe(10)
        snap = json.loads(r.to_json())
        assert snap["a"] == {"type": "counter", "value": 2.0}
        assert snap["b"] == {"type": "gauge", "value": 1.5}
        assert snap["c"]["type"] == "histogram" and snap["c"]["count"] == 1
    assert get_registry() is not r  # previous default restored


# ---------------------------------------------------------------------------
# the disabled-path contract: a fit makes ZERO tracer calls
# ---------------------------------------------------------------------------

def test_fit_with_obs_disabled_makes_zero_tracer_calls(monkeypatch):
    from repro.methods import fit as methods_fit

    calls = {"span": 0, "_record": 0}
    orig_span, orig_record = Tracer.span, Tracer._record

    def counting_span(self, *a, **k):
        calls["span"] += 1
        return orig_span(self, *a, **k)

    def counting_record(self, *a, **k):
        calls["_record"] += 1
        return orig_record(self, *a, **k)

    monkeypatch.setattr(Tracer, "span", counting_span)
    monkeypatch.setattr(Tracer, "_record", counting_record)
    result = methods_fit(lowrank(), 4, niters=2, key=KEY)
    assert float(result.fit) > 0
    assert calls == {"span": 0, "_record": 0}


# ---------------------------------------------------------------------------
# Session wiring: one trace across the pipeline
# ---------------------------------------------------------------------------

def traced_session(tmp_path, **obs_kw):
    obs_kw.setdefault("enabled", True)
    obs_kw.setdefault("trace_dir", str(tmp_path / "trace"))
    cfg = RunConfig(method=MethodConfig(rank=4, niters=3, seed=0),
                    obs=ObsConfig(**obs_kw))
    return Session.from_config(cfg, tensor=lowrank())


def test_session_fit_writes_trace_and_metrics(tmp_path):
    with scoped_registry():
        sess = traced_session(tmp_path)
        sess.fit()
        assert "# provenance:" in sess.plan_report()
    d = tmp_path / "trace"
    events = read_trace(d / TRACE_FILENAME)
    names = {e["name"] for e in events}
    assert {"stage.ingest", "stage.plan", "stage.fit",
            "iteration", "mttkrp", "epilogue", "sort"} <= names
    iters = [e for e in events if e.get("name") == "iteration"]
    assert len(iters) == 3
    assert all(e["args"]["method"] == "cp_als" for e in iters)
    # mttkrp spans carry the per-mode impl the planner chose
    m = next(e for e in events if e.get("name") == "mttkrp")
    assert "impl" in m["args"] and "mode" in m["args"]
    metrics = json.loads((d / METRICS_FILENAME).read_text())
    assert metrics["fit.iterations"]["value"] == 3.0
    assert metrics["fit.iteration_ms"]["count"] == 3


def test_session_split_routines_trace(tmp_path):
    with scoped_registry():
        sess = traced_session(tmp_path, routines="split")
        sess.fit()
    events = read_trace(tmp_path / "trace" / TRACE_FILENAME)
    names = {e["name"] for e in events}
    # the paper's full Table-III routine set replaces the fused epilogue
    assert {"ata", "mttkrp", "inverse", "norm", "fit"} <= names
    assert "epilogue" not in names


def test_session_obs_disabled_no_tracer(tmp_path):
    cfg = RunConfig(method=MethodConfig(rank=4, niters=2))
    sess = Session.from_config(cfg, tensor=lowrank())
    sess.fit()
    assert sess.tracer() is None
    assert sess.export_obs() is None


def test_serve_latency_histogram(tmp_path):
    with scoped_registry() as registry:
        sess = traced_session(tmp_path)
        sess.fit()
        bench = sess.serve_handle().benchmark(queries=64, batch=16)
        lat = bench["latency_ms"]
        assert lat["count"] > 0
        assert lat["p50"] is not None and lat["p99"] is not None
        assert lat["p50"] <= lat["p99"]
        assert registry.histogram("serve.query_ms").count > 0
    # query spans only land in the export AFTER serve ran — rewrite it
    sess.export_obs()
    events = read_trace(tmp_path / "trace" / TRACE_FILENAME)
    assert any(e.get("name") == "serve.query" for e in events)


# ---------------------------------------------------------------------------
# metric feeds: straggler escalations, cache hit/miss provenance
# ---------------------------------------------------------------------------

def test_straggler_escalations_feed_registry():
    from repro.dist.straggler import StragglerMonitor

    with scoped_registry() as registry:
        monitor = StragglerMonitor(window=4, threshold=1.5, patience=2)
        for _ in range(3):
            monitor.record(0, 1.0)
            monitor.record(1, 1.0)
            monitor.record(2, 10.0)
        assert monitor.check() == {2: "slow"}
        assert monitor.check() == {2: "persistent"}
        snap = registry.snapshot()
        assert snap["straggler.slow"]["value"] == 1.0
        assert snap["straggler.persistent"]["value"] == 1.0


def test_provenance_footer_variants():
    from repro.utils.report import _provenance_footer

    warm = _provenance_footer({"cache_hit": True,
                               "ingest": {"hits": 1, "misses": 0},
                               "autotune": {"hits": 3, "misses": 1}})
    assert "ingest-cache warm (hits=1 misses=0)" in warm
    assert "autotune hits=3 misses=1" in warm
    cold = _provenance_footer({"cache_hit": False,
                               "ingest": {"hits": 0, "misses": 1}})
    assert "ingest-cache cold" in cold
    none = _provenance_footer({"cache_hit": False})
    assert "no ingest cache" in none


def test_ingest_cache_counters_feed_registry(tmp_path):
    from repro.ingest import ingest

    with scoped_registry() as registry:
        ingest(lowrank(), cache=tmp_path / "cache")  # cold: miss + store
        ingest(lowrank(), cache=tmp_path / "cache")  # warm: hit
        snap = registry.snapshot()
        assert snap["ingest.cache.miss"]["value"] == 1.0
        assert snap["ingest.cache.hit"]["value"] == 1.0


# ---------------------------------------------------------------------------
# ObsConfig validation + round-trip
# ---------------------------------------------------------------------------

def test_obs_config_validation():
    with pytest.raises(ConfigError, match="obs.sample_rate"):
        ObsConfig(sample_rate=0.0)
    with pytest.raises(ConfigError, match="obs.routines"):
        ObsConfig(routines="both")
    with pytest.raises(ConfigError, match="obs.enabled"):
        ObsConfig(trace_dir="/tmp/x")  # tracing off would write nothing


def test_obs_config_roundtrip():
    cfg = RunConfig(obs=ObsConfig(enabled=True, trace_dir="artifacts/t",
                                  sample_rate=0.5, routines="split",
                                  xla_annotations=False))
    back = RunConfig.from_json(cfg.to_json())
    assert back == cfg and back.obs.routines == "split"


# ---------------------------------------------------------------------------
# the trace report + CLI
# ---------------------------------------------------------------------------

def test_routine_breakdown_aggregation():
    us = 1e6  # event times are microseconds
    events = [
        {"name": "stage.fit", "ph": "X", "ts": 0, "dur": 10 * us, "args": {}},
        {"name": "iteration", "ph": "X", "ts": 0, "dur": 5 * us,
         "args": {"method": "cp_als"}},
        {"name": "mttkrp", "ph": "X", "ts": 0, "dur": 2 * us,
         "args": {"mode": 0, "impl": "segment"}},
        {"name": "mttkrp", "ph": "X", "ts": 2 * us, "dur": 1 * us,
         "args": {"mode": 1, "impl": "gather_scatter"}},
        {"name": "epilogue", "ph": "X", "ts": 3 * us, "dur": 2 * us,
         "args": {"mode": 0}},
        {"name": "not-a-routine", "ph": "X", "ts": 0, "dur": 9 * us,
         "args": {}},
        {"name": "ignored", "ph": "M", "args": {}},
    ]
    s = routine_breakdown(events)
    assert s["fit_s"] == pytest.approx(10.0)
    assert s["iterations"] == 1 and s["methods"] == ["cp_als"]
    mt = s["routines"]["mttkrp"]
    assert mt["calls"] == 2 and mt["total_s"] == pytest.approx(3.0)
    assert mt["modes"][0]["impl"] == "segment"
    assert mt["modes"][1]["impl"] == "gather_scatter"
    # unaccounted = fit stage minus every routine total (5s here)
    assert s["unaccounted_s"] == pytest.approx(10.0 - 5.0)


def test_trace_report_and_cli(tmp_path, capsys):
    from repro.api.cli import main

    with scoped_registry():
        sess = traced_session(tmp_path)
        sess.fit()
    report = trace_report(tmp_path / "trace")
    assert "| routine |" in report and "mttkrp" in report
    assert "# metrics" in report
    assert "sort" in report  # the pre-loop CSF sort is its own row

    assert main(["trace", str(tmp_path / "trace")]) == 0
    out = capsys.readouterr().out
    assert "| routine |" in out and "% fit" in out

    assert main(["trace", str(tmp_path / "nope")]) == 2
    assert "no trace.jsonl" in capsys.readouterr().err


def test_trace_report_missing_dir(tmp_path):
    with pytest.raises(FileNotFoundError, match="--trace-dir"):
        trace_report(tmp_path / "missing")


def test_cli_trace_flags_map_to_obs_config(tmp_path):
    import argparse

    from repro.api.cli import config_from_args

    base = dict(config=None, source=None, dataset="yelp", scale=None,
                data_seed=None, reorder=None, compact=None, cache=None,
                impl=None, calibrate=None, method=None, rank=[4], iters=None,
                tol=None, seed=None, option=None, executor=None,
                checkpoint_dir=None, checkpoint_every=None, monitor=None,
                n_chunks=None, chunk_nnz=None)
    ns = argparse.Namespace(**base, trace_dir=str(tmp_path / "t"),
                            trace_split=True)
    cfg = config_from_args(ns)
    assert cfg.obs.enabled and cfg.obs.trace_dir == str(tmp_path / "t")
    assert cfg.obs.routines == "split"
    # no trace flags -> obs stays fully default (disabled)
    ns = argparse.Namespace(**base, trace_dir=None, trace_split=None)
    assert config_from_args(ns).obs == ObsConfig()
