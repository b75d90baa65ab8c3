"""repro.dist — the distributed-runtime layer.

The paper's performance study is, at heart, a study of how a sparse
CP-ALS runtime schedules irregular work across parallel workers; its
named future work is SPLATT's medium-grained *distributed* algorithm.
``repro.core.distributed`` implements that algorithm with ``shard_map``;
this package supplies the runtime plumbing around it:

``collectives``
    The single mesh/axis vocabulary: which mesh axes partition CP-ALS
    rows vs columns, how the pod axis joins the row partition, and the
    psum / reduce-scatter / all-gather helpers used inside ``shard_map``
    bodies.  Consumed by both ``repro.core.distributed`` and
    ``repro.launch.mesh``.

``straggler``
    :class:`StragglerMonitor` — windowed per-worker wall-time tracking
    that flags persistently slow hosts.  Worker imbalance is the central
    hazard of distributed sparse tensor work (irregular non-zero
    distributions make some ranks structurally slower); the monitor
    makes it observable at the driver loop.

See ``docs/architecture.md`` ("The distributed layer") for how these
pieces stack on top of the core CP-ALS kernels.
"""
from .collectives import (CPAxes, MODEL_AXIS, axis_product, cpals_axes,
                          gather_rows, make_mesh, pgram, pnormalize_columns,
                          scatter_rows)
from .straggler import StragglerMonitor

__all__ = [
    "CPAxes", "MODEL_AXIS", "axis_product", "cpals_axes",
    "gather_rows", "make_mesh", "pgram", "pnormalize_columns",
    "scatter_rows",
    "StragglerMonitor",
]
