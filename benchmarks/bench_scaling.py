"""Paper Figs 9/10 (scalability 1->32 threads): on this substrate the
parallel-resource axis is host devices; we run the distributed medium-grained
CP-ALS MTTKRP path over 1/2/4/8 host devices in subprocesses and report the
per-iteration wall time (near-linear scaling is the paper's claim).

A CPU simulation by design: the children are pinned to the CPU backend, so
on a TPU host they neither see one chip in place of the simulated mesh nor
contend for it.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from .common import emit

_CHILD = """
import time, json
import jax, jax.numpy as jnp
from repro.core import random_sparse
from repro.core.distributed import dist_cp_als
from repro.dist.collectives import make_mesh
n = {n}
mesh = make_mesh(({rows}, {cols}), ("data", "model"))
t = random_sparse((3000, 2500, 2000), 150_000, jax.random.PRNGKey(0))
t0 = time.time()
dist_cp_als(t, 16, mesh, niters=1)   # compile+first
t1 = time.time()
dist_cp_als(t, 16, mesh, niters=3)
el = (time.time() - t1) / 3
print(json.dumps({{"iter_s": el}}))
"""


def run():
    rows = []
    root = Path(__file__).resolve().parents[1]
    base = None
    for n, (r, c) in ((1, (1, 1)), (2, (2, 1)), (4, (2, 2)), (8, (4, 2))):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
                   PYTHONPATH=str(root / "src"))
        code = textwrap.dedent(_CHILD.format(n=n, rows=r, cols=c))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        if out.returncode != 0:
            rows.append({"bench": "scaling", "devices": n, "iter_ms": "FAIL"})
            continue
        iter_s = json.loads(out.stdout.strip().splitlines()[-1])["iter_s"]
        if base is None:
            base = iter_s
        rows.append({"bench": "scaling", "devices": n,
                     "iter_ms": round(iter_s * 1e3, 1),
                     "speedup": round(base / iter_s, 2)})
    return rows


if __name__ == "__main__":
    emit(run())
