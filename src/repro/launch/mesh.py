"""The production mesh of the distributed CP-ALS dry-run.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run target is
  single-pod:  (data=16, model=16)          = 256 chips (TPU v5e pod)
  multi-pod:   (pod=2, data=16, model=16)   = 512 chips

Axis *names* come from ``repro.dist.collectives`` — the same vocabulary the
distributed CP-ALS path resolves its row/column grid from.  See
``docs/architecture.md``.
"""
from __future__ import annotations

from jax.sharding import Mesh

from repro.dist.collectives import DATA_AXIS, MODEL_AXIS, POD_AXIS, make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ((POD_AXIS, DATA_AXIS, MODEL_AXIS) if multi_pod
            else (DATA_AXIS, MODEL_AXIS))
    return make_mesh(shape, axes)
