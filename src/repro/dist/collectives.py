"""Shared collectives vocabulary: mesh-axis resolution + psum plumbing.

``repro.core.distributed`` (medium-grained CP-ALS) and ``repro.launch.mesh``
(the dry-run's production mesh) both need the same facts about a mesh:
which axes partition rows vs columns, how the pod axis joins the row
partition, and how a column-normalize or Gram reduce is phrased inside
``shard_map``.  This module is the single home for that vocabulary so both
agree by construction.

Conventions (see ``launch/mesh.py`` for the physical shapes):

  * ``"model"`` is always the *column* axis of the CP-ALS grid;
  * every other axis — ``("data",)`` single-pod, ``("pod", "data")``
    multi-pod — is a *row* axis.  The pod axis joining the row partition
    is what makes one reduce spec express "psum within the pod over ICI
    + across pods over DCN".

The reduce helpers (:func:`pnormalize_columns`, :func:`pgram`,
:func:`scatter_rows`, :func:`gather_rows`) are for use *inside*
``shard_map`` bodies; the resolution helpers (:func:`cpals_axes`,
:func:`axis_product`) are host-side and touch no jax device state.  See
``docs/architecture.md`` ("The distributed layer").
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array
AxisName = Union[str, tuple]

MODEL_AXIS = "model"
DATA_AXIS = "data"
POD_AXIS = "pod"


# ---------------------------------------------------------------------------
# the mesh constructor
# ---------------------------------------------------------------------------

def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with Auto axis types.  Every mesh in the repo goes
    through here: ``jax.make_mesh`` defaults to Explicit axes, under which
    indexing a sharded array needs an explicit ``out_sharding``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# ---------------------------------------------------------------------------
# host-side axis resolution
# ---------------------------------------------------------------------------

def axis_product(mesh: Mesh, axes: Sequence[str]) -> int:
    """Number of devices along ``axes`` (product of mesh extents)."""
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) \
        if axes else 1


@dataclasses.dataclass(frozen=True)
class CPAxes:
    """Resolved CP-ALS grid axes for a mesh.

    ``row`` partitions mode-0 factor rows (and the non-zero blocks' first
    grid dim); ``col`` partitions mode-1; ``all_axes`` is the whole mesh
    (mode-2 reduce scope).  ``spec()`` helpers phrase the matching
    PartitionSpecs so callers never re-spell the tuples.
    """
    row: tuple
    col: str
    n_row: int
    n_col: int

    @property
    def all_axes(self) -> tuple:
        return self.row + (self.col,)

    @property
    def n_all(self) -> int:
        return self.n_row * self.n_col

    def grid_spec(self) -> P:
        """Spec of the (n_row, n_col, ...) partitioned non-zero blocks."""
        return P(self.row, self.col)

    def row_spec(self) -> P:
        return P(self.row)

    def col_spec(self) -> P:
        return P(self.col)

    def all_spec(self) -> P:
        return P(self.all_axes)


def cpals_axes(mesh: Mesh) -> CPAxes:
    """Resolve the CP-ALS row/column axes of ``mesh``: ``"model"`` is the
    column axis, everything else (``data``, optionally led by ``pod``)
    partitions rows."""
    if MODEL_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {MODEL_AXIS!r} axis")
    row = tuple(a for a in mesh.axis_names if a != MODEL_AXIS)
    return CPAxes(row=row, col=MODEL_AXIS,
                  n_row=axis_product(mesh, row),
                  n_col=mesh.shape[MODEL_AXIS])


# ---------------------------------------------------------------------------
# shard_map-body collectives
# ---------------------------------------------------------------------------

def pgram(mat: Array, axis_names: AxisName) -> Array:
    """Gram matrix of a row-sharded factor: psum of the local A^T A, at
    f32 precision (``repro.core.gram`` says why)."""
    return jax.lax.psum(
        jax.lax.dot_general(mat, mat, (((0,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST),
        axis_names)


def pnormalize_columns(mat: Array, axis_names: AxisName, *,
                       kind: str = "2"):
    """Column-normalize a row-sharded matrix; returns ``(mat, lam)``.

    ``kind="2"``: lam = global column 2-norms (psum of squares);
    ``kind="max"``: lam = max(1, global column max-abs) — SPLATT's
    first-iteration norm.  Zero columns are left untouched (unit lam).
    """
    if kind == "max":
        lam = jax.lax.pmax(jnp.max(jnp.abs(mat), axis=0), axis_names)
        lam = jnp.maximum(lam, 1.0)
    else:
        lam = jnp.sqrt(jax.lax.psum(jnp.sum(mat * mat, axis=0), axis_names))
    safe = jnp.where(lam == 0.0, 1.0, lam)
    return mat / safe[None, :], lam


def scatter_rows(x: Array, axes: Sequence[AxisName]) -> Array:
    """Reduce-scatter ``x`` along dim 0 over each axis group in order —
    half the wire of psum + slice.  Block layout after scattering over
    ``(row, col)`` is row-major in the grid (block id = r * n_col + c),
    matching ``P(row + (col,))``."""
    for a in axes:
        x = jax.lax.psum_scatter(x, a, scatter_dimension=0, tiled=True)
    return x


def gather_rows(x: Array, axes: Sequence[AxisName]) -> Array:
    """Inverse of :func:`scatter_rows`: all-gather dim 0 over the same
    axis groups, applied in reverse order so the row-major block layout
    is reassembled exactly."""
    for a in reversed(tuple(axes)):
        x = jax.lax.all_gather(x, a, axis=0, tiled=True)
    return x
