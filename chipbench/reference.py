"""The plain reference the timed path is compared with, and its control.

The reference is float64 numpy on the host, from the generated coordinates
and values alone: it imports nothing of the program and takes nothing the
program made but the answers under test.

Fit cells: one ALS sweep of the timed path, from the state before it to
the state after it, is checked mode by mode.  For mode ``n`` the reference
forms the MTTKRP ``M`` and the Gram Hadamard ``V`` from the factors the
program held at that point of the sweep (modes before ``n`` already
updated), and measures how far the program's new factor ``A`` is from
solving the normal equations, with the column scales fitted:
``min_D ||A D V - M|| / ||M||``.  That is a backward error, so the badly
conditioned ``V`` of a rank-35 model of a random tensor does not blow it up
the way it blows up a factor-to-factor comparison.  The reported fit is
compared with the fit of the program's own returned model, computed
exactly.

Serving: every checked ``values_at`` answer and ``top_k`` score against
float64, relative to the largest sum of absolute rank-one terms involved;
a ``top_k`` answer also has to hold items that all score at least the true
k-th best.

The control is this reference put in the program's place at the next
precision below the configuration's: every matrix product, which the
configuration states at ``Precision.HIGHEST``, in three bfloat16 passes
(``Precision.HIGH``), written out so that it does the same on a CPU as on
a TPU; ``values_at``, plain float32 with no product to lower, in
bfloat16.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 18
# threads of the float64 check, each holding a few CHUNK x rank float64
# temporaries
THREADS = min(12, os.cpu_count() or 1)


class Reference:
    """float64 evaluations over one sparse tensor in coordinate form."""

    def __init__(self, inds: np.ndarray, vals: np.ndarray, dims):
        self.inds = inds
        self.vals = vals.astype(np.float64)
        self.dims = tuple(int(d) for d in dims)
        self._order: dict = {}

    def _sorted_by(self, mode: int):
        if mode not in self._order:
            rows = self.inds[:, mode]
            perm = np.argsort(rows, kind="stable")
            self._order[mode] = (perm, rows[perm])
        return self._order[mode]

    def _mttkrp_chunk(self, factors_t, mode: int, lo: int):
        """The rows of non-zeros ``lo:lo + CHUNK`` in ``mode``'s order and
        their partial sums.  ``factors_t`` are the factors transposed: a
        sum along the last axis of a (rank, nnz) product keeps to numpy's
        fast path and lets go of the interpreter lock."""
        perm, rows = self._sorted_by(mode)
        sel = perm[lo:lo + CHUNK]
        r = rows[lo:lo + CHUNK]
        prod = np.repeat(self.vals[sel][None, :], factors_t[0].shape[0],
                         axis=0)
        for m, a_t in enumerate(factors_t):
            if m != mode:
                prod *= np.take(a_t, self.inds[sel, m], axis=1)
        starts = np.flatnonzero(np.r_[True, r[1:] != r[:-1]])
        return r[starts], np.add.reduceat(prod, starts, axis=1).T

    def mttkrp(self, factors, mode: int, pool=None) -> np.ndarray:
        """``mode``'s MTTKRP, its chunks spread over ``pool``'s threads
        where one is given."""
        self._sorted_by(mode)
        out = np.zeros((self.dims[mode], factors[0].shape[1]))
        chunk = partial(self._mttkrp_chunk,
                        [np.ascontiguousarray(a.T) for a in factors], mode)
        starts = range(0, self.vals.shape[0], CHUNK)
        for r, sums in (pool.map(chunk, starts) if pool else map(chunk,
                                                                  starts)):
            out[r] += sums
        return out

    def norm_sq(self) -> float:
        return float(np.dot(self.vals, self.vals))

    def _inner_chunk(self, factors_t, lmbda, lo: int) -> float:
        prod = np.repeat(lmbda[:, None], min(CHUNK, self.vals.shape[0] - lo),
                         axis=1)
        for m, a_t in enumerate(factors_t):
            prod *= np.take(a_t, self.inds[lo:lo + CHUNK, m], axis=1)
        return float(self.vals[lo:lo + CHUNK] @ prod.sum(axis=0))

    def inner(self, factors, lmbda, pool=None) -> float:
        """<X, X_hat> of the model ``(factors, lmbda)``."""
        chunk = partial(self._inner_chunk,
                        [np.ascontiguousarray(a.T) for a in factors], lmbda)
        starts = range(0, self.vals.shape[0], CHUNK)
        return sum(pool.map(chunk, starts) if pool else map(chunk, starts))

    def fit(self, factors, lmbda, pool=None) -> float:
        """The model's fit ``1 - ||X - X_hat|| / ||X||``, exactly."""
        had = np.ones((lmbda.shape[0],) * 2)
        for a in factors:
            had *= a.T @ a
        norm_x = self.norm_sq()
        resid = norm_x + float(lmbda @ had @ lmbda) \
            - 2.0 * self.inner(factors, lmbda, pool)
        return 1.0 - np.sqrt(max(resid, 0.0)) / np.sqrt(norm_x)


def _f64(arrays):
    return [np.asarray(a, dtype=np.float64) for a in arrays]


def normal_eq_residual(a: np.ndarray, m: np.ndarray, v: np.ndarray) -> float:
    """``min_D ||A diag(D) V - M||_F / ||M||_F``: how far ``A`` is from
    solving ``X V = M`` up to the scale of each column."""
    gram = (a.T @ a) * (v @ v.T)
    rhs = np.einsum("ir,is,rs->r", a, m, v)
    d = np.linalg.lstsq(gram, rhs, rcond=None)[0]
    return float(np.linalg.norm((a * d) @ v - m) / np.linalg.norm(m))


def sweep_check(ref: Reference, before, after, lmbda, fit,
                threads: int = THREADS) -> dict:
    """Check the sweep that took ``before`` (factors) to ``after``
    (factors, ``lmbda``, reported ``fit``).  Returns the compared numbers
    and each mode's residual."""
    before, after = _f64(before), _f64(after)
    lmbda = np.asarray(lmbda, dtype=np.float64)
    order = len(after)

    def mode_residual(n, pool):
        held = [after[m] if m < n else before[m] for m in range(order)]
        v = np.ones((lmbda.shape[0],) * 2)
        for m in range(order):
            if m != n:
                v *= held[m].T @ held[m]
        return normal_eq_residual(after[n], ref.mttkrp(held, n, pool), v)

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(ref._sorted_by, range(order)))
        modes = [mode_residual(n, pool) for n in range(order)]
        true_fit = ref.fit(after, lmbda, pool)
    return {"sweep_residual": max(modes),
            "fit_err": abs(float(fit) - true_fit),
            "mode_residuals": modes, "true_fit": true_fit}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def values_at_err(factors, lmbda, coords: np.ndarray, got) -> float:
    terms = np.broadcast_to(lmbda, (coords.shape[0], lmbda.shape[0])).copy()
    for m, a in enumerate(factors):
        terms *= a[coords[:, m]]
    scale = max(float(np.abs(terms).sum(axis=1).max()), 1e-300)
    return float(np.abs(np.asarray(got, np.float64) - terms.sum(axis=1))
                 .max() / scale)


def top_k_err(factors, lmbda, users: np.ndarray, scores, items, k: int,
              user_mode: int = 0, item_mode: int = 1) -> np.ndarray:
    """Per user: the worse of the largest score error and the amount by
    which the weakest returned item falls short of the true k-th best, both
    relative to the largest sum of absolute terms; ``inf`` for repeated
    items."""
    w = lmbda.copy()
    for m, a in enumerate(factors):
        if m not in (user_mode, item_mode):
            w = w * a.sum(axis=0)
    u = factors[user_mode][users] * w
    item_f = factors[item_mode]
    want = u @ item_f.T
    scale = np.maximum((np.abs(u) @ np.abs(item_f).T).max(axis=1), 1e-300)
    kth = np.partition(want, -k, axis=1)[:, -k]
    items = np.asarray(items)
    picked = np.take_along_axis(want, items, axis=1)
    err = np.maximum(np.abs(np.asarray(scores, np.float64) - picked)
                     .max(axis=1), np.maximum(kth - picked.min(axis=1), 0.0))
    err = err / scale
    repeated = np.array([len(set(r.tolist())) != k for r in items])
    return np.where(repeated, np.inf, err)


# ---------------------------------------------------------------------------
# the control: the reference in float32 with three-pass bfloat16 products
# ---------------------------------------------------------------------------


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def dot3(a, b):
    """``a @ b`` as ``Precision.HIGH`` computes it: three bfloat16
    products (hi*hi + hi*lo + lo*hi) accumulated in float32."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    dot = partial(jnp.matmul, preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


@partial(jax.jit, static_argnames=("mode", "num_rows"))
def _control_mttkrp(inds, vals, factors, *, mode, num_rows):
    prod = vals[:, None]
    for m, a in enumerate(factors):
        if m != mode:
            prod = prod * a[inds[:, m]]
    return jax.ops.segment_sum(prod, inds[:, mode], num_segments=num_rows)


def control_sweep(inds, vals, factors, *, norm_kind: str = "2"):
    """One CP-ALS sweep of the reference algorithm in float32 with every
    matrix product in three bfloat16 passes; returns ``(factors, lmbda,
    fit)`` as the program's sweep does."""
    factors = [jnp.asarray(a, jnp.float32) for a in factors]
    inds, vals = jnp.asarray(inds), jnp.asarray(vals, jnp.float32)
    order = len(factors)
    grams = [dot3(a.T, a) for a in factors]
    rank = factors[0].shape[1]
    m_mat = lam = None
    for n in range(order):
        m_mat = _control_mttkrp(inds, vals, tuple(factors), mode=n,
                                num_rows=factors[n].shape[0])
        v = jnp.ones((rank, rank), jnp.float32)
        for m in range(order):
            if m != n:
                v = v * grams[m]
        chol = jnp.linalg.cholesky(v + 1e-12 * jnp.eye(rank))
        v_inv = jax.scipy.linalg.cho_solve((chol, True), jnp.eye(rank))
        a = dot3(m_mat, v_inv)
        lam = (jnp.sqrt(jnp.sum(a * a, axis=0)) if norm_kind == "2"
               else jnp.maximum(jnp.max(jnp.abs(a), axis=0), 1.0))
        factors[n] = a / jnp.where(lam == 0.0, 1.0, lam)
        grams[n] = dot3(factors[n].T, factors[n])
    had = jnp.ones((rank, rank), jnp.float32)
    for g in grams:
        had = had * g
    norm_x = jnp.sum(vals * vals)
    norm_z = jnp.sum(jnp.outer(lam, lam) * had)
    inner = jnp.sum(jnp.sum(m_mat * factors[-1], axis=0) * lam)
    fit = 1.0 - jnp.sqrt(jnp.maximum(norm_x + norm_z - 2.0 * inner, 0.0)) \
        / jnp.sqrt(norm_x)
    return tuple(factors), lam, fit


def control_top_k(factors, lmbda, users, k: int, user_mode: int = 0,
                  item_mode: int = 1):
    """``(scores, items)`` of the reference scoring with three-pass
    bfloat16 products."""
    w = jnp.asarray(lmbda, jnp.float32)
    for m, a in enumerate(factors):
        if m not in (user_mode, item_mode):
            w = w * jnp.sum(a, axis=0)
    u = factors[user_mode][jnp.asarray(users)] * w
    return jax.lax.top_k(dot3(u, factors[item_mode].T), k)


def control_values_at(factors, lmbda, coords):
    """``values_at`` of the reference in bfloat16: it has no matrix product
    for ``Precision.HIGH`` to lower, so its next precision below plain
    float32 is bfloat16 operands (summed in float32)."""
    prod = jnp.broadcast_to(jnp.asarray(lmbda, jnp.bfloat16),
                            (coords.shape[0], lmbda.shape[0]))
    for m, a in enumerate(factors):
        prod = prod * a[jnp.asarray(coords[:, m])].astype(jnp.bfloat16)
    return jnp.sum(prod.astype(jnp.float32), axis=1)
