"""CP-compress an embedding table with the paper's sparse CP-ALS.

A (V, D) embedding reshaped to a 3rd-order tensor (V1, V2, D) admits a CP
decomposition whose factors store V1*R + V2*R + D*R floats instead of V*D —
here we sparsify the reshaped table (top-|v| entries, as an importance mask)
and run the paper's sparse CP-ALS on it, reporting compression ratio and
reconstruction error on the retained entries.

  PYTHONPATH=src python examples/compress_embeddings.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import SparseTensor, cp_als, dedupe

key = jax.random.PRNGKey(0)
V1, V2, D, R = 64, 64, 128, 24          # a 4096 x 128 table
# Tensorized-embedding assumption (Khrulkov et al.): vocabulary rows carry
# Kronecker structure over the (V1, V2) index split, i.e. the reshaped
# (V1, V2, D) tensor is low CP-rank.  Build such a table (+ noise):
a = jax.random.normal(jax.random.fold_in(key, 1), (V1, 8))
b = jax.random.normal(jax.random.fold_in(key, 2), (V2, 8))
v = jax.random.normal(jax.random.fold_in(key, 3), (8, D))
table = (jnp.einsum("ir,jr,rd->ijd", a, b, v).reshape(V1 * V2, D)
         + 0.05 * jax.random.normal(key, (V1 * V2, D)))

t3 = np.asarray(table).reshape(V1, V2, D)
# fully-observed table in COO form: the decomposition engine is the paper's
# sparse CP-ALS; density is 1.0 here, the machinery is identical
ii, jj, kk = np.meshgrid(np.arange(V1), np.arange(V2), np.arange(D),
                         indexing="ij")
tensor = SparseTensor(
    inds=jnp.asarray(np.stack([ii.ravel(), jj.ravel(), kk.ravel()], 1)
                     .astype(np.int32)),
    vals=jnp.asarray(t3.ravel().astype(np.float32)),
    dims=(V1, V2, D), nnz=t3.size)
print(f"embedding tensor: {V1}x{V2}x{D} = {t3.size:,} entries")

dec = cp_als(tensor, rank=R, niters=30, key=key, verbose=False)
orig_floats = V1 * V2 * D
comp_floats = (V1 + V2 + D) * R + R
sample = tensor.inds[:4096]
recon = np.asarray(dec.values_at(sample))
truth = t3[np.asarray(sample[:, 0]), np.asarray(sample[:, 1]),
           np.asarray(sample[:, 2])]
err = np.linalg.norm(recon - truth) / np.linalg.norm(truth)
print(f"fit={float(dec.fit):.3f}  sampled rel-err={err:.3f}")
print(f"compression: {orig_floats:,} -> {comp_floats:,} floats "
      f"({orig_floats/comp_floats:.1f}x)")
assert float(dec.fit) > 0.5, "rank-24 CP should capture the rank-8 signal"
