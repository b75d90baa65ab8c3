"""repro.dist — the distributed-runtime layer.

The paper's performance study is, at heart, a study of how a sparse
CP-ALS runtime schedules irregular work across parallel workers; its
named future work is SPLATT's medium-grained *distributed* algorithm.
``repro.core.distributed`` implements that algorithm with ``shard_map``;
this package supplies the runtime plumbing around it, shared with the LM
training path:

``collectives``
    The single mesh/axis vocabulary: which mesh axes partition CP-ALS
    rows vs columns, pod-aware batch axes, and the psum / reduce-scatter
    / all-gather helpers used inside ``shard_map`` bodies.  Consumed by
    both ``repro.core.distributed`` and ``repro.launch.mesh``.

``straggler``
    :class:`StragglerMonitor` — windowed per-worker wall-time tracking
    that flags persistently slow hosts.  Worker imbalance is the central
    hazard of distributed sparse tensor work (irregular non-zero
    distributions make some ranks structurally slower); the monitor
    makes it observable at the driver loop.

``compress``
    int8 gradient quantization with error-feedback residuals over
    arbitrary pytrees — halves (vs bf16) or quarters (vs f32) the bytes
    the data-parallel all-reduce moves.  Opt-in via
    ``make_train_step(..., grad_compress=True)``.

See ``docs/architecture.md`` ("The distributed layer") for how these
pieces stack on top of the core CP-ALS kernels.
"""
from .collectives import (CPAxes, MODEL_AXIS, axis_product, batch_axes,
                          cpals_axes, gather_rows, make_mesh, pgram,
                          pnormalize_columns, scatter_rows)
from .compress import (compress_grads_int8, decompress_grads_int8,
                       init_error_feedback)
from .straggler import StragglerMonitor

__all__ = [
    "CPAxes", "MODEL_AXIS", "axis_product", "batch_axes", "cpals_axes",
    "gather_rows", "make_mesh", "pgram", "pnormalize_columns",
    "scatter_rows",
    "compress_grads_int8", "decompress_grads_int8", "init_error_feedback",
    "StragglerMonitor",
]
