"""Pallas TPU kernel: blocked syrk (G = A^T A) for tall-skinny factors.

The paper's "Mat A^TA" routine is BLAS syrk via OpenBLAS; on TPU the
tall-skinny (I x R, R <= a few hundred) Gram product is a reduction over row
blocks that fits the MXU directly.  Grid is the row-block index; the single
R x R output tile stays in VMEM across all steps and accumulates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(a_ref, out_ref):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    blk = a_ref[...].astype(jnp.float32)  # (BLK, RP)
    out_ref[...] += jax.lax.dot(
        blk.T, blk, preferred_element_type=jnp.float32
    )


def syrk_pallas_call(a: Array, *, blk: int = 512, interpret: bool) -> Array:
    rows, rp = a.shape
    if rows % blk:
        raise ValueError(f"rows ({rows}) must be padded to blk ({blk})")
    nblocks = rows // blk
    out = pl.pallas_call(
        _kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((blk, rp), lambda k: (k, 0))],
        out_specs=pl.BlockSpec((rp, rp), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, rp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(a)
    return out
