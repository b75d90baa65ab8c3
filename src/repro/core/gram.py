"""Dense rank-R linear algebra for CP-ALS.

These are the paper's non-MTTKRP routines from Table III:

  * ``gram``            — A^T A             ("Mat A^TA", BLAS syrk)
  * ``hadamard_grams``  — V = hadamard of other modes' Grams
  * ``solve_cholesky``  — A = M V^-1        ("Inverse", LAPACK potrf/potrs)
  * ``solve_gram``      — same solve, inverse-then-GEMM (fused epilogue)
  * ``normalize``       — column norms -> lambda ("Mat norm")
  * ``kruskal_fit``     — decomposition fit  ("CPD fit")

All matrices here are I x R or R x R with small R (paper uses R=35), so these
are jnp-native; the Pallas syrk kernel (kernels/syrk_pallas.py) is an optional
drop-in for ``gram`` on tall-skinny inputs.

Their products run at ``Precision.HIGHEST``: a TPU's default precision
rounds f32 matmul operands to bfloat16, which would move the fit of an f32
decomposition away from its f32 reference.  At rank R they are a small
share of a sweep next to the MTTKRP.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

Array = jax.Array

# Ridge added to V's diagonal before Cholesky: SPLATT relies on potrf on a
# PSD-by-construction matrix; in f32 a tiny jitter keeps cho_factor stable on
# nearly-rank-deficient iterates without changing converged results.
CHOLESKY_RIDGE = 1e-12

HIGHEST = jax.lax.Precision.HIGHEST


# Rows of a factor that one contraction of ``gram`` sums.  A single
# HIGHEST contraction over a tall factor loses float32 accuracy with its
# length: on a TPU v5e the Gram of a 244,268-row factor read 1.2e-5 off
# float64 (relative Frobenius), of a 6,066-row one 1.4e-7.  A taller
# factor's Gram is summed over chunks of this many rows, and the chunks'
# Grams pairwise: 6.5e-8 to 8.4e-8 at 244,268 rows.
GRAM_ROWS = 512


def gram(a: Array, *, impl: str = "jnp") -> Array:
    """G = A^T A (syrk analogue). impl='pallas' uses the blocked kernel."""
    if impl == "pallas":
        from repro.kernels import ops as kops

        return kops.syrk(a)
    if a.shape[0] > GRAM_ROWS:
        return _gram_chunked(a)
    # contract dim 0 directly: with a materialized ``a.T`` the eager and
    # the jitted product may round differently, and a resumed fit (grams
    # recomputed eagerly) would drift from the uninterrupted one
    return jax.lax.dot_general(a, a, (((0,), (0,)), ((), ())),
                               precision=HIGHEST)


def _gram_chunked(a: Array) -> Array:
    """``gram`` of a factor taller than ``GRAM_ROWS``: one Gram per chunk of
    rows (the last zero-padded), then the chunks' Grams added in pairs,
    level by level (a level of odd length is padded with a zero Gram)."""
    rows, rank = a.shape
    chunks = -(-rows // GRAM_ROWS)
    a = jnp.pad(a, ((0, chunks * GRAM_ROWS - rows), (0, 0)))
    a = a.reshape(chunks, GRAM_ROWS, rank)
    parts = jax.lax.dot_general(a, a, (((1,), (1,)), ((0,), (0,))),
                                precision=HIGHEST)
    while parts.shape[0] > 1:
        if parts.shape[0] % 2:
            parts = jnp.concatenate([parts, jnp.zeros_like(parts[:1])])
        parts = parts[0::2] + parts[1::2]
    return parts[0]


def hadamard_grams(grams: Sequence[Array], skip_mode: int) -> Array:
    """V = hadamard_{m != skip_mode} G_m  (lines 4/7/10 of Alg. 1)."""
    out = None
    for m, g in enumerate(grams):
        if m == skip_mode:
            continue
        out = g if out is None else out * g
    assert out is not None
    return out


def solve_cholesky(m_mat: Array, v: Array) -> Array:
    """A = M V^{-1} via Cholesky (potrf+potrs analogue, not an explicit pinv).

    V is symmetric PSD (hadamard of Gram matrices); solve V X^T = M^T.
    """
    r = v.shape[0]
    v = v + CHOLESKY_RIDGE * jnp.eye(r, dtype=v.dtype)
    c = jax.scipy.linalg.cho_factor(v, lower=False)
    return jax.scipy.linalg.cho_solve(c, m_mat.T).T


def solve_gram(m_mat: Array, v: Array) -> Array:
    """A = M V^{-1}, formulated for tall M: invert the R x R Gram hadamard
    via Cholesky, then apply it as a single GEMM.

    Mathematically identical to :func:`solve_cholesky` (V is symmetric PSD),
    but the expensive step is an (I x R)(R x R) matmul instead of a pair of
    triangular solves with I right-hand sides.  On CPU the triangular solves
    run single-threaded and scalar through LAPACK while the GEMM vectorizes,
    so for the ALS shapes (I in the thousands, R ~ 35) this is an order of
    magnitude faster; the O(R^3) explicit inverse is noise at these ranks.
    The fused epilogue uses this; :func:`solve_cholesky` remains the
    routine-by-routine "Inverse" (paper Table III) implementation.
    """
    r = v.shape[0]
    eye = jnp.eye(r, dtype=v.dtype)
    c = jax.scipy.linalg.cho_factor(v + CHOLESKY_RIDGE * eye, lower=False)
    v_inv = jax.scipy.linalg.cho_solve(c, eye)
    return jnp.matmul(m_mat, v_inv, precision=HIGHEST)


def column_norms(a: Array, *, kind: str) -> Array:
    """kind='max' (SPLATT's first-iteration norm) or '2' (subsequent)."""
    if kind == "max":
        return jnp.maximum(jnp.max(jnp.abs(a), axis=0), 1.0)
    if kind == "2":
        return jnp.sqrt(jnp.sum(a * a, axis=0))
    raise ValueError(f"unknown norm kind {kind!r}")


def normalize(a: Array, *, kind: str) -> tuple[Array, Array]:
    """Column-normalize; returns (A_normalized, lambda). Zero-safe."""
    lam = column_norms(a, kind=kind)
    safe = jnp.where(lam == 0.0, 1.0, lam)
    return a / safe[None, :], lam


def kruskal_norm_sq(lmbda: Array, grams: Sequence[Array]) -> Array:
    """||X_hat||^2 = sum( (lambda lambda^T) . hadamard_m G_m )."""
    had = None
    for g in grams:
        had = g if had is None else had * g
    return jnp.sum((lmbda[:, None] * lmbda[None, :]) * had)


def kruskal_inner(m_last: Array, a_last: Array, lmbda: Array) -> Array:
    """<X, X_hat> = sum_r lambda_r sum_i M_last[i,r] A_last[i,r].

    ``m_last`` is the final mode's MTTKRP output of this iteration and
    ``a_last`` the (normalized) updated factor — SPLATT's p_tt_inner trick:
    the inner product falls out of work already done, no extra pass over X.
    """
    return jnp.sum(jnp.sum(m_last * a_last, axis=0) * lmbda)


def kruskal_fit(
    norm_x_sq: Array, lmbda: Array, grams: Sequence[Array], m_last: Array, a_last: Array
) -> Array:
    """fit = 1 - sqrt(max(||X||^2 + ||X_hat||^2 - 2<X,X_hat>, 0)) / ||X||."""
    norm_z_sq = kruskal_norm_sq(lmbda, grams)
    inner = kruskal_inner(m_last, a_last, lmbda)
    resid_sq = jnp.maximum(norm_x_sq + norm_z_sq - 2.0 * inner, 0.0)
    return 1.0 - jnp.sqrt(resid_sq) / jnp.sqrt(norm_x_sq)
