"""Three-term roofline from a compiled dry-run artifact.

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = wire_bytes_per_device / ICI link_bw

cost_analysis() on the SPMD-partitioned module is PER-DEVICE (verified
empirically: reported flops ~= global/num_devices for a known matmul), so no
further division by chip count.  Collective bytes are NOT in cost_analysis:
we parse the optimized HLO (compiled.as_text()) for
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
ops (including their async -start forms), take per-device result shapes, and
convert to ring-algorithm wire bytes:

  all-reduce       2 * B * (g-1)/g        (B = per-device block bytes)
  all-gather       B_out * (g-1)/g        (B_out = gathered result bytes)
  reduce-scatter   B_out * (g-1)          (B_out = scattered result bytes)
  all-to-all       B * (g-1)/g
  collective-perm  B

The peaks come from :data:`PEAKS`, keyed by the ``device_kind`` jax
reports; a kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Any

import numpy as np


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""
    flops: float      # bf16 FLOP/s
    hbm_bw: float     # HBM bytes/s
    hbm_bytes: float  # HBM capacity
    ici_bw: float     # bytes/s per ICI link
    source: str


# keyed by ``jax.Device.device_kind``
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        # 1,600 Gbit/s of interconnect per chip over its four ICI links
        ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e'"),
}


def peaks_for(kind: str) -> Peaks:
    """The peaks of ``kind`` (a ``device_kind`` string); raises for a kind
    the table does not hold."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {kind!r}; known: "
            f"{tuple(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?P<result>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)"
    r"(?P<start>-start)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return 1


def parse_collectives(hlo: str) -> list[dict]:
    """One record per collective op: kind, result bytes (per device), group
    size, wire bytes (per device, ring algorithm)."""
    out = []
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        if "-done" in line.split("=")[0]:
            continue
        kind = m.group("op")
        b = _shape_bytes(m.group("result"))
        g = max(1, _group_size(line))
        if kind == "all-reduce":
            wire = 2.0 * b * (g - 1) / g
        elif kind == "all-gather":
            wire = b * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = b * (g - 1)
        elif kind == "all-to-all":
            wire = b * (g - 1) / g
        else:  # collective-permute
            wire = float(b)
        out.append({"kind": kind, "bytes": b, "group": g, "wire": wire})
    return out


def collective_summary(colls: list[dict]) -> dict:
    agg: dict[str, dict] = defaultdict(lambda: {"count": 0, "bytes": 0.0,
                                                "wire": 0.0})
    for c in colls:
        a = agg[c["kind"]]
        a["count"] += 1
        a["bytes"] += c["bytes"]
        a["wire"] += c["wire"]
    return dict(agg)


@dataclasses.dataclass
class Roofline:
    flops: float                 # per device
    bytes_accessed: float        # per device
    wire_bytes: float            # per device
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float           # useful flops the caller counts
    useful_ratio: float          # model_flops / (flops * chips)
    collectives: dict
    bound_s: float

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def analyze_values(*, flops: float, bytes_accessed: float, wire_bytes: float,
                   collectives: dict, n_chips: int, model_flops: float,
                   kind: str) -> Roofline:
    peaks = peaks_for(kind)
    compute_s = flops / peaks.flops
    memory_s = bytes_accessed / peaks.hbm_bw
    collective_s = wire_bytes / peaks.ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    useful = model_flops / max(flops * n_chips, 1.0)
    return Roofline(
        flops=flops, bytes_accessed=bytes_accessed, wire_bytes=wire_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=model_flops, useful_ratio=useful,
        collectives=collectives, bound_s=max(terms.values()),
    )


def analyze(cost: dict, hlo: str, *, n_chips: int, model_flops: float,
            kind: str) -> Roofline:
    """Roofline of one compiled program: ``cost`` is its
    ``cost_analysis()`` dict, ``hlo`` its optimized text, ``kind`` the
    ``device_kind`` whose peaks bound it."""
    colls = parse_collectives(hlo)
    return analyze_values(
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes accessed", 0.0)),
        wire_bytes=sum(c["wire"] for c in colls),
        collectives=collective_summary(colls),
        n_chips=n_chips, model_flops=model_flops, kind=kind)
