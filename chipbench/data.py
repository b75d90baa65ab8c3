"""Inputs made from ``--seed``: the sparse tensor and the tenants' models.

Both are drawn on the device in one jitted call each.  The tensor follows
the paper replica's distribution (each mode's index is ``floor(u^(1 + skew)
* dim)`` for a uniform ``u``, so ``skew`` 0 is uniform and a larger skew
piles non-zeros onto low indices; values are uniform in [0.1, 1)), is
sorted by coordinates on the device, and has its duplicate coordinates
summed on the host: the program is handed unique coordinates, as its fit
formula needs.

The coordinates come from the configuration's ``structure_seed``, as a data
set's are fixed, and ``--seed`` draws the values (and, in the fit, the
initial factors).  The served tenants' models come from ``structure_seed``
too, as a deployment's are fixed.  So every seed gives the program the same shapes and the
same work, and a second run of a cell in a checkout compiles nothing: the
sorted workspace's padded sizes, and with them every compiled shape,
follow from where the non-zeros lie.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed32(seed: int, salt: int = 0) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from any whole number
    (``--seed`` may pass 32 signed bits)."""
    return int(np.random.SeedSequence([int(seed), salt])
               .generate_state(1)[0] & 0x7FFFFFFF)


@dataclasses.dataclass
class Tensor:
    """The generated tensor, on the host and as the program's input."""

    inds: np.ndarray      # (nnz, order) int32, sorted by coordinates
    vals: np.ndarray      # (nnz,) float32
    dims: tuple
    program: object       # repro.core.coo.SparseTensor on the device

    @property
    def nnz(self) -> int:
        return int(self.vals.shape[0])


@partial(jax.jit, static_argnames=("dims", "nnz", "skew"))
def _draw(structure_key, value_key, *, dims, nnz, skew):
    keys = jax.random.split(structure_key, len(dims))
    cols = []
    for m, d in enumerate(dims):
        u = jax.random.uniform(keys[m], (nnz,), minval=1e-6, maxval=1.0)
        x = u ** (1.0 + skew) if skew > 0.0 else u
        cols.append(jnp.minimum((x * d).astype(jnp.int32), d - 1))
    vals = jax.random.uniform(value_key, (nnz,), dtype=jnp.float32,
                              minval=0.1, maxval=1.0)
    out = jax.lax.sort((*cols, vals), num_keys=len(dims))
    cols, vals = out[:-1], out[-1]
    # a row that repeats the coordinates of the row before it
    repeat = jnp.ones((nnz - 1,), dtype=bool)
    for c in cols:
        repeat = repeat & (c[1:] == c[:-1])
    starts = jnp.concatenate([jnp.ones((1,), dtype=bool), ~repeat])
    return jnp.stack(cols, axis=1), vals, starts


def make_tensor(dims, nnz: int, skew: float, structure_seed: int,
                seed: int) -> Tensor:
    from repro.core.coo import SparseTensor

    dims = tuple(int(d) for d in dims)
    inds, vals, starts = jax.device_get(
        _draw(jax.random.PRNGKey(seed32(structure_seed, 3)),
              jax.random.PRNGKey(seed32(seed)), dims=dims, nnz=int(nnz),
              skew=float(skew)))
    idx = np.flatnonzero(starts)
    inds = np.ascontiguousarray(inds[idx])
    vals = np.add.reduceat(vals, idx).astype(np.float32)
    program = SparseTensor(inds=jnp.asarray(inds), vals=jnp.asarray(vals),
                           dims=dims, nnz=int(vals.shape[0]))
    return Tensor(inds=inds, vals=vals, dims=dims, program=program)


@partial(jax.jit, static_argnames=("dims", "rank", "tenants"))
def _draw_models(key, *, dims, rank, tenants):
    out = []
    for k in jax.random.split(key, tenants):
        ks = jax.random.split(k, len(dims) + 1)
        factors = tuple(jax.random.uniform(ks[m], (d, rank))
                        for m, d in enumerate(dims))
        lmbda = jax.random.uniform(ks[-1], (rank,), minval=0.5, maxval=1.5)
        out.append((factors, lmbda))
    return out


def make_models(dims, rank: int, tenants: int, seed: int) -> list:
    """``tenants`` non-negative rank-``rank`` CP models at ``dims``, as
    ``(factors, lmbda)`` device arrays: random weights in place of fits."""
    return _draw_models(jax.random.PRNGKey(seed32(seed, 1)),
                        dims=tuple(int(d) for d in dims), rank=int(rank),
                        tenants=int(tenants))
