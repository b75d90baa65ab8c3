"""End-to-end system behaviour: the paper's pipeline from tensor to
decomposition through the public API, factorized embeddings, and orders
beyond the paper's third."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cp_als, paper_dataset, random_sparse


def test_paper_pipeline_end_to_end():
    """Synthetic YELP-shaped tensor -> 20 ALS iterations at rank 35 with
    per-routine timers (the paper's Table III protocol, CPU-scaled)."""
    t = paper_dataset("yelp", jax.random.PRNGKey(0), scale=0.003)
    # warm the jit caches so timers measure execution, not compilation
    cp_als(t, rank=35, niters=2, impl="segment", key=jax.random.PRNGKey(1),
           timers={})
    timers = {}
    dec = cp_als(t, rank=35, niters=20, impl="segment",
                 key=jax.random.PRNGKey(1), timers=timers)
    assert 0.0 < float(dec.fit) <= 1.0
    assert all(k in timers for k in ("sort", "mttkrp", "ata", "inverse",
                                     "norm", "fit"))
    # MTTKRP must dominate the dense-algebra routines (the paper's core
    # claim).  norm is excluded from the comparison: at CPU bench scale its
    # wall time is scheduler-noise-sensitive on a loaded 1-core box; the
    # full breakdown lives in bench_output.txt (bench_cpals_routines).
    assert timers["mttkrp"] > timers["ata"], timers
    assert timers["mttkrp"] > timers["fit"], timers


def test_factorized_embedding_contact_point():
    """CP-ALS compresses a Kronecker-structured embedding table to a
    quarter of its floats at fit > 0.95."""
    key = jax.random.PRNGKey(0)
    v1, v2, d, r = 16, 16, 32, 12
    a = jax.random.normal(jax.random.fold_in(key, 1), (v1, 6))
    b = jax.random.normal(jax.random.fold_in(key, 2), (v2, 6))
    w = jax.random.normal(jax.random.fold_in(key, 3), (6, d))
    t3 = np.asarray(jnp.einsum("ir,jr,rd->ijd", a, b, w))
    ii, jj, kk = np.meshgrid(np.arange(v1), np.arange(v2), np.arange(d),
                             indexing="ij")
    from repro.core import SparseTensor
    tensor = SparseTensor(
        inds=jnp.asarray(np.stack([ii.ravel(), jj.ravel(), kk.ravel()], 1)
                         .astype(np.int32)),
        vals=jnp.asarray(t3.ravel().astype(np.float32)),
        dims=(v1, v2, d), nnz=t3.size)
    dec = cp_als(tensor, rank=r, niters=25, key=key)
    assert float(dec.fit) > 0.95, float(dec.fit)
    # compression is real
    assert (v1 + v2 + d) * r + r < v1 * v2 * d / 4


def test_multi_order_support():
    """Order-4 decomposition (beyond the paper's 3rd-order restriction)."""
    t = random_sparse((10, 9, 8, 7), 600, jax.random.PRNGKey(4))
    dec = cp_als(t, rank=4, niters=5, impl="gather_scatter",
                 key=jax.random.PRNGKey(5))
    assert len(dec.factors) == 4
    assert 0.0 <= float(dec.fit) <= 1.0
