"""COO sparse tensor container + synthetic generators + FROSTT .tns IO.

This is the framework's canonical in-memory sparse tensor format. The paper
(SPLATT-in-Chapel) reads FROSTT-style ``.tns`` text files and sorts non-zeros
into CSF as a pre-processing step; here COO is the load-time format and
:mod:`repro.core.csf` holds the per-mode sorted ("CSF-flat") layout.

All arrays are static-shape (JAX requirement): ``nnz`` may be padded to a block
multiple with explicit zero values pointing at a dummy row index so every
downstream op is shape-stable under jit.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SparseTensor:
    """Order-N sparse tensor in coordinate format.

    inds: (nnz, order) int32 indices, one column per mode.
    vals: (nnz,) float values. Padding entries have val == 0.
    dims: static tuple of mode lengths.
    nnz:  static logical (unpadded) non-zero count.
    """

    inds: Array
    vals: Array
    dims: tuple[int, ...]
    nnz: int

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return (self.inds, self.vals), (self.dims, self.nnz)

    @classmethod
    def tree_unflatten(cls, aux, children):
        inds, vals = children
        dims, nnz = aux
        return cls(inds=inds, vals=vals, dims=dims, nnz=nnz)

    # -- basics ------------------------------------------------------------
    @property
    def order(self) -> int:
        return len(self.dims)

    @property
    def padded_nnz(self) -> int:
        return self.vals.shape[0]

    @property
    def density(self) -> float:
        return float(self.nnz) / float(np.prod([float(d) for d in self.dims]))

    def norm(self) -> Array:
        """Frobenius norm of the tensor (padding vals are zero)."""
        return jnp.sqrt(jnp.sum(self.vals.astype(jnp.float64) ** 2)).astype(
            self.vals.dtype
        )

    def to_dense(self) -> Array:
        """Densify (tests only — small tensors)."""
        out = jnp.zeros(self.dims, dtype=self.vals.dtype)
        return out.at[tuple(self.inds[:, m] for m in range(self.order))].add(
            self.vals
        )

    def pad_to(self, multiple: int) -> "SparseTensor":
        """Pad nnz up to a multiple; padding rows index 0 with value 0."""
        n = self.padded_nnz
        target = ((n + multiple - 1) // multiple) * multiple
        if target == n:
            return self
        pad = target - n
        inds = jnp.concatenate(
            [self.inds, jnp.zeros((pad, self.order), dtype=self.inds.dtype)]
        )
        vals = jnp.concatenate([self.vals, jnp.zeros((pad,), dtype=self.vals.dtype)])
        return SparseTensor(inds=inds, vals=vals, dims=self.dims, nnz=self.nnz)


# ---------------------------------------------------------------------------
# Synthetic generators
# ---------------------------------------------------------------------------

def random_sparse(
    dims: Sequence[int],
    nnz: int,
    key: Array,
    *,
    dtype=jnp.float32,
    skew: float = 0.0,
) -> SparseTensor:
    """Uniform (skew=0) or power-law-skewed random sparse tensor.

    ``skew`` > 0 concentrates non-zeros on low indices per mode (zipf-ish),
    reproducing the collision-heavy regime of the paper's YELP data set where
    SPLATT is forced onto its mutex-pool MTTKRP path.  skew == 0 reproduces the
    collision-light NELL-2-like regime ("no-lock" path).
    """
    dims = tuple(int(d) for d in dims)
    keys = jax.random.split(key, len(dims) + 1)
    cols = []
    for m, d in enumerate(dims):
        u = jax.random.uniform(keys[m], (nnz,), minval=1e-6, maxval=1.0)
        if skew > 0.0:
            # inverse-CDF of a truncated power law: heavier mass at low idx
            x = u ** (1.0 + skew)
        else:
            x = u
        cols.append(jnp.minimum((x * d).astype(jnp.int32), d - 1))
    inds = jnp.stack(cols, axis=1)
    vals = jax.random.uniform(keys[-1], (nnz,), dtype=dtype, minval=0.1, maxval=1.0)
    return dedupe(SparseTensor(inds=inds, vals=vals, dims=dims, nnz=nnz))


def dedupe(t: SparseTensor) -> SparseTensor:
    """Collapse duplicate coordinates (summing values) — SPLATT and the fit
    formula (sum vals^2 == ||X||_F^2) assume unique coordinates.  Host-side,
    build-time only."""
    inds = np.asarray(t.inds[: t.nnz])
    vals = np.asarray(t.vals[: t.nnz])
    lin = np.ravel_multi_index(tuple(inds[:, m] for m in range(t.order)), t.dims)
    uniq, inv = np.unique(lin, return_inverse=True)
    if uniq.shape[0] == inds.shape[0]:
        return t
    summed = np.zeros(uniq.shape[0], dtype=vals.dtype)
    np.add.at(summed, inv, vals)
    new_inds = np.stack(np.unravel_index(uniq, t.dims), axis=1).astype(np.int32)
    return SparseTensor(
        inds=jnp.asarray(new_inds),
        vals=jnp.asarray(summed),
        dims=t.dims,
        nnz=int(uniq.shape[0]),
    )


def from_factors(
    factors: Sequence[Array],
    nnz: int,
    key: Array,
    *,
    noise: float = 0.0,
) -> SparseTensor:
    """Sample ``nnz`` entries of a known low-rank CP tensor (ground truth for
    convergence tests): val = sum_r prod_m A_m[i_m, r] (+ gaussian noise)."""
    dims = tuple(int(a.shape[0]) for a in factors)
    keys = jax.random.split(key, len(dims) + 1)
    cols = [
        jax.random.randint(keys[m], (nnz,), 0, d, dtype=jnp.int32)
        for m, d in enumerate(dims)
    ]
    inds = jnp.stack(cols, axis=1)
    prod = jnp.ones((nnz, factors[0].shape[1]), dtype=factors[0].dtype)
    for m, a in enumerate(factors):
        prod = prod * a[inds[:, m]]
    vals = jnp.sum(prod, axis=1)
    if noise > 0.0:
        vals = vals + noise * jax.random.normal(keys[-1], (nnz,), dtype=vals.dtype)
    return dedupe(SparseTensor(inds=inds, vals=vals, dims=dims, nnz=nnz))


# Paper Table I shapes (dims, nnz). Used by benchmarks/configs; the synthetic
# generator reproduces shape/density, not the actual review data.
PAPER_DATASETS: dict[str, tuple[tuple[int, ...], int, float]] = {
    # name: (dims, nnz, skew)  — skew chosen so YELP-like tensors exercise the
    # collision/mutex path the paper analyzes in §V-D.2, NELL-2-like does not.
    "yelp": ((41_000, 11_000, 75_000), 8_000_000, 1.5),
    "rate-beer": ((27_000, 105_000, 262_000), 62_000_000, 1.0),
    "beer-advocate": ((31_000, 61_000, 182_000), 63_000_000, 1.0),
    "nell-2": ((12_000, 9_000, 29_000), 77_000_000, 0.0),
    "netflix": ((480_000, 18_000, 2_000), 100_000_000, 0.5),
}

# The paper's CP rank for every data set.
PAPER_RANK = 35

# Launcher workload ids (``--arch`` of ``repro.launch.dryrun``/``serve``,
# ``repro dryrun --workload``, dry-run artifact cell ids) -> PAPER_DATASETS key.
PAPER_WORKLOADS: dict[str, str] = {
    "cpals-yelp": "yelp",
    "cpals-nell2": "nell-2",
    "cpals-netflix": "netflix",
}


def paper_dataset(name: str, key: Array, *, scale: float = 1.0) -> SparseTensor:
    """Synthetic tensor with the published shape/density of a paper data set.

    ``scale`` < 1 shrinks nnz (and dims proportionally to keep density) for
    CPU-sized benchmark runs; scale == 1.0 is the full published shape.
    """
    dims, nnz, skew = PAPER_DATASETS[name]
    if scale != 1.0:
        dims = tuple(max(8, int(d * scale ** (1 / 3))) for d in dims)
        nnz = max(64, int(nnz * scale))
    return random_sparse(dims, nnz, key, skew=skew)


# ---------------------------------------------------------------------------
# FROSTT .tns IO — thin wrappers over the streaming reader/writer in
# repro.ingest.reader (comment/blank tolerance, arity validation, explicit
# dims override, duplicate policy, vectorized formatting).  Imported lazily
# to keep the coo -> ingest dependency one-way at import time.
# ---------------------------------------------------------------------------

_warned_legacy_io = False


def _warn_legacy_io() -> None:
    global _warned_legacy_io
    if not _warned_legacy_io:
        import warnings

        warnings.warn(
            "repro.core.read_tns/write_tns are legacy re-exports; new code "
            "should use repro.ingest (reader / ingest()) or the repro.api "
            "DataConfig surface", DeprecationWarning, stacklevel=3)
        _warned_legacy_io = True


def read_tns(path: str, *, dtype=np.float32, dims=None,
             duplicates: str = "sum") -> SparseTensor:
    """Read FROSTT text (1-indexed ``i j k val`` lines).  See
    :func:`repro.ingest.reader.read_tns` — pass ``dims=`` to keep trailing
    empty slices (inference shrinks dims to max index + 1).

    .. deprecated:: use ``repro.ingest`` — warns once per process."""
    from repro.ingest import reader

    _warn_legacy_io()
    return reader.read_tns(path, dtype=dtype, dims=dims,
                           duplicates=duplicates)


def write_tns(path: str, t: SparseTensor) -> None:
    """Write FROSTT text with vectorized, round-trip-exact formatting
    (:func:`repro.ingest.reader.write_tns`).

    .. deprecated:: use ``repro.ingest`` — warns once per process."""
    from repro.ingest import reader

    _warn_legacy_io()
    reader.write_tns(path, t)
