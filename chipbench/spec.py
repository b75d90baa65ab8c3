"""``BENCHMARK.json`` and the files it names.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
each is a JSON file found by its name alone:

* ``chipbench/configs/<config>.json``: the deployment (sizes, rank, plan,
  dtype, the limits of the comparison that decides ``correct``);
* ``chipbench/traffic/<traffic>.json``: the mix's parameters, whose
  ``kind`` picks the generator (``sweeps`` or ``open_loop``);
* ``chipbench/metrics/<metric>.py``: the reader of one per-layer metric, a
  ``read(ctx)`` that returns a number or ``None`` where it finds nothing.

So a later change adds a configuration, a mix or a metric by adding a file
and an entry, and edits none of these.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
PKG = "chipbench"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list   # the end-to-end metric entries this cell reports
    per_layer: list    # the per-layer metric entries this cell reports
    root: Path

    def reader(self, metric: str):
        """The ``read(ctx)`` of a per-layer metric's file."""
        return load_reader(self.root, metric)


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"no {path}") from None


def config_path(root: Path, name: str) -> Path:
    return Path(root) / PKG / "configs" / f"{name}.json"


def traffic_path(root: Path, name: str) -> Path:
    return Path(root) / PKG / "traffic" / f"{name}.json"


def metric_path(root: Path, name: str) -> Path:
    return Path(root) / PKG / "metrics" / f"{name}.py"


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"no file {path}") from None


def load_reader(root: Path, metric: str):
    path = metric_path(root, metric)
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        f"{PKG}_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"one of {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names no configuration "
                        f"{w['config']!r}")
    config = _read_json(Path(root) / configs[w["config"]]["file"])
    traffic = _read_json(traffic_path(root, w["traffic"]))
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, workload)],
                root=Path(root))


def validate(bench: dict, root: Path = ROOT) -> list:
    """Every fault the harness can see in ``bench``: names, units, sources,
    files that have to exist.  Empty when the file is sound."""
    faults = []
    names = set()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench.get(section, []):
            n = entry.get("name", "")
            if not NAME.match(n):
                faults.append(f"{section}: bad name {n!r}")
            if n in names:
                faults.append(f"{section}: name {n!r} used twice")
            names.add(n)
    for m in bench.get("end_to_end", []) + bench.get("per_layer", []):
        if not UNIT.match(m.get("unit", "")):
            faults.append(f"{m['name']}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            faults.append(f"{m['name']}: better must be lower or higher")
        if m.get("source") not in SOURCES:
            faults.append(f"{m['name']}: bad source {m.get('source')!r}")
    for m in bench.get("end_to_end", []):
        if m.get("source") not in ("host_clock", "device_trace"):
            faults.append(f"{m['name']}: an end-to-end metric is taken by "
                          f"the benchmark itself")
    e2e = {m["name"] for m in bench.get("end_to_end", [])}
    for m in bench.get("per_layer", []):
        if m.get("moves") not in e2e:
            faults.append(f"{m['name']}: moves no end-to-end metric")
        if not metric_path(root, m["name"]).is_file():
            faults.append(f"{m['name']}: no reader file")
    configs = {c["name"]: c for c in bench.get("configs", [])}
    for c in configs.values():
        for key in c.get("reduced", []):
            if not NAME.match(key):
                faults.append(f"{c['name']}: bad reduced key {key!r}")
        if not (Path(root) / c.get("file", "")).is_file():
            faults.append(f"{c['name']}: no file {c.get('file')!r}")
    for w in bench.get("workloads", []):
        for key in ("config", "traffic"):
            if not NAME.match(w.get(key, "")):
                faults.append(f"{w['name']}: bad {key} {w.get(key)!r}")
        if w.get("config") not in configs:
            faults.append(f"{w['name']}: no configuration {w.get('config')!r}")
        if not traffic_path(root, w.get("traffic", "")).is_file():
            faults.append(f"{w['name']}: no traffic file")
        if w.get("chips") not in (1, 4):
            faults.append(f"{w['name']}: chips must be 1 or 4")
    return faults
