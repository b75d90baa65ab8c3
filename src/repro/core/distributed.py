"""Medium-grained distributed CP-ALS (shard_map over the production mesh).

This implements the paper's named future work — SPLATT's medium-grained
distributed algorithm [Smith & Karypis, IPDPS'16] — on the TPU mesh:

  * the (I x J x K) tensor is partitioned over the 2-D logical grid
    (rows of mode-0 over the 'data' axis x rows of mode-1 over 'model'):
    device (d, t) owns non-zeros with i in I-block_d and j in J-block_t;
  * factor A is row-sharded over 'data', B over 'model', C replicated;
  * each mode-n update does a LOCAL MTTKRP on owned non-zeros, then a psum
    over the mesh axes whose devices hold partial rows (mode-0: 'model';
    mode-1: 'data'; mode-2: both) — the all-reduce that SPLATT does with
    MPI rides the ICI torus here;
  * Gram matrices / column norms / fit are tiny (R x R, R) psums.

Multi-pod: the 'pod' axis joins 'data' as the mode-0 row axis, so the same
spec expresses reduce within the pod + all-reduce across pods over DCN.

Axis resolution and the psum/reduce-scatter phrasing live in
``repro.dist.collectives`` (shared with ``launch/mesh.py``); this module
only contains what is CP-ALS specific: the host-side non-zero partitioner
and the shard_map iteration body.  See ``docs/architecture.md``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs import trace as obs_trace
from repro.dist.collectives import (cpals_axes, gather_rows, pgram,
                                    pnormalize_columns, scatter_rows)

from .coo import PAPER_DATASETS, PAPER_RANK, PAPER_WORKLOADS, SparseTensor
from .gram import (column_norms, gram, hadamard_grams, kruskal_fit,
                   solve_cholesky, normalize)

Array = jax.Array


# the local MTTKRP reductions the shard_map iteration body can express —
# the candidate set every dist-facing planner/validator must respect
DIST_IMPLS = ("gather_scatter", "segment")


# ---------------------------------------------------------------------------
# host-side partitioner
# ---------------------------------------------------------------------------

def partition_tensor(t: SparseTensor, n_row: int, n_col: int,
                     *, pad_factor: float = 1.05):
    """Partition non-zeros over an (n_row x n_col) grid by (mode-0 block,
    mode-1 block).  Returns host arrays (inds (n_row, n_col, L, 3), vals
    (n_row, n_col, L)) and the padded dims.  Within a grid block the
    non-zeros keep their input order; padding entries have val 0 and point
    at the block's first local rows."""
    assert t.order == 3, "medium-grained partitioner is 3rd-order (like SPLATT)"
    inds = np.asarray(t.inds[: t.nnz])
    vals = np.asarray(t.vals[: t.nnz])
    i_p = -(-t.dims[0] // n_row) * n_row
    j_p = -(-t.dims[1] // n_col) * n_col
    bi, bj = i_p // n_row, j_p // n_col
    di = inds[:, 0] // bi
    dj = inds[:, 1] // bj

    counts = np.zeros((n_row, n_col), dtype=np.int64)
    np.add.at(counts, (di, dj), 1)
    cap = int(np.ceil(counts.max() * pad_factor)) if counts.max() else 1

    out_i = np.zeros((n_row, n_col, cap, 3), dtype=np.int32)
    out_v = np.zeros((n_row, n_col, cap), dtype=vals.dtype)
    # default padding coordinates: block-local row 0 of each mode block
    for r in range(n_row):
        out_i[r, :, :, 0] = r * bi
    for c in range(n_col):
        out_i[:, c, :, 1] = c * bj

    # stable sort by grid block, then each entry's rank inside its block
    order = np.lexsort((dj, di))
    r, c = di[order], dj[order]
    block_start = np.concatenate([[0], np.cumsum(counts.ravel())[:-1]])
    k = np.arange(order.shape[0]) - block_start[r * n_col + c]
    out_i[r, c, k] = inds[order]
    out_v[r, c, k] = vals[order]
    return out_i, out_v, (i_p, j_p, t.dims[2])


# ---------------------------------------------------------------------------
# one distributed ALS iteration (shard_map body)
# ---------------------------------------------------------------------------

def _local_mttkrp(inds, vals, rows_local, fa, fb, fc, num_rows: int,
                  impl: str = "scatter"):
    """Local MTTKRP over this device's non-zeros.
    rows_local: which column of inds indexes the OUTPUT rows (local ids);
    fa/fb/fc are the gather sources for the three modes (local or global).
    ``impl``: "scatter" (XLA scatter-add — the mutex/atomic analogue) or
    "segment" (segment-sum — the no-lock reduction the planner picks for
    contention-heavy modes); both are exact, the planner chooses by regime."""
    prod = vals[:, None].astype(fa.dtype)
    sources = (fa, fb, fc)
    for m in range(3):
        if m == rows_local:
            continue
        prod = prod * sources[m][inds[:, m]]
    if impl == "segment":
        return jax.ops.segment_sum(prod, inds[:, rows_local],
                                   num_segments=num_rows)
    out = jnp.zeros((num_rows, prod.shape[1]), dtype=prod.dtype)
    return out.at[inds[:, rows_local]].add(prod, mode="drop")


def _local_impls_of(plan) -> tuple[str, str, str]:
    """Map a DecompPlan's per-mode impls onto what the shard_map body can
    express (sorted workspaces don't survive the per-device partitioning, so
    'segment' means a local segment reduction, everything else scatter-add)."""
    return tuple("segment" if p.impl == "segment" else "scatter"
                 for p in plan.modes)


def make_dist_iteration(mesh: Mesh, dims_p, rank: int, *, norm_kind: str = "2",
                        shard_c: bool = False,
                        local_impls: tuple[str, str, str] = ("scatter",) * 3):
    """Builds the jitted shard_map'd single-iteration function.

    Row axes: mode-0 over ('pod','data') [or ('data',)], mode-1 over 'model'.

    ``local_impls``: the plan's per-mode local MTTKRP strategy (see
    ``_local_mttkrp``).

    ``shard_c``: the optimized mode-2 layout (EXPERIMENTS.md §Perf).  The
    baseline replicates C and its dense solve/gram on every device (faithful
    to SPLATT's medium-grained layout for the shortest mode, but ~20x
    redundant per-device dense work at 256 chips); shard_c row-shards C over
    the WHOLE mesh, replaces the mode-2 psum with a psum_scatter (half the
    wire), solves only local rows, and all-gathers C once per iteration.
    """
    ax = cpals_axes(mesh)
    row_ax, col_ax, all_ax = ax.row, ax.col, ax.all_axes
    i_p, j_p, k_dim = dims_p
    bi, bj = i_p // ax.n_row, j_p // ax.n_col
    if shard_c:
        assert k_dim % ax.n_all == 0, (k_dim, ax.n_all)

    in_specs = (
        ax.grid_spec(),          # inds (n_row, n_col, L, 3)
        ax.grid_spec(),          # vals (n_row, n_col, L)
        ax.row_spec(),           # A (i_p, R) row-sharded
        ax.col_spec(),           # B (j_p, R) row-sharded over model
        ax.all_spec() if shard_c else P(),   # C rows
        P(),                     # norm_x_sq scalar
    )
    out_specs = (ax.row_spec(), ax.col_spec(),
                 ax.all_spec() if shard_c else P(), P(), P())

    def body(inds, vals, a_blk, b_blk, c_in, norm_x_sq):
        if shard_c:
            # rebuild the full C for the mode-0/1 gathers (10s of MB):
            # the exact inverse of the reduce-scatter order below.
            c_full = gather_rows(c_in, (row_ax, col_ax))
        else:
            c_full = c_in
        inds = inds[0, 0]
        vals = vals[0, 0]
        # localize indices into the block-sharded factors
        row_id = jax.lax.axis_index(row_ax)
        col_id = jax.lax.axis_index(col_ax)
        li = inds[:, 0] - row_id * bi
        lj = inds[:, 1] - col_id * bj
        lk = inds[:, 2]
        linds = jnp.stack([li, lj, lk], axis=1)

        def grams_all(a, b, c):
            ga = pgram(a, row_ax)
            gb = pgram(b, col_ax)
            if shard_c:
                gc = pgram(c_in, all_ax)
            else:
                gc = gram(c)
            return ga, gb, gc

        ga, gb, gc = grams_all(a_blk, b_blk, c_full)

        # ---- mode 0: partials summed over the 'model' axis ----
        v0 = gb * gc
        m0 = _local_mttkrp(linds, vals, 0, a_blk, b_blk, c_full, bi,
                           impl=local_impls[0])
        m0 = jax.lax.psum(m0, col_ax)
        a_new = solve_cholesky(m0, v0)
        a_new, lam = pnormalize_columns(a_new, row_ax, kind=norm_kind)
        ga = pgram(a_new, row_ax)

        # ---- mode 1: partials summed over the row axes ----
        v1 = ga * gc
        m1 = _local_mttkrp(linds, vals, 1, a_new, b_blk, c_full, bj,
                           impl=local_impls[1])
        m1 = jax.lax.psum(m1, row_ax)
        b_new = solve_cholesky(m1, v1)
        b_new, lam = pnormalize_columns(b_new, col_ax, kind=norm_kind)
        gb = pgram(b_new, col_ax)

        # ---- mode 2 ----
        v2 = ga * gb
        m2 = _local_mttkrp(linds, vals, 2, a_new, b_new, c_full, k_dim,
                           impl=local_impls[2])
        if shard_c:
            # optimized: half-wire reduce+scatter, local dense solve
            m2_blk = scatter_rows(m2, (row_ax, col_ax))
            c_new = solve_cholesky(m2_blk, v2)
            c_new, lam = pnormalize_columns(c_new, all_ax, kind=norm_kind)
            gc = pgram(c_new, all_ax)
            # blockwise fit: <X,Xhat> from local rows, summed over the mesh
            from .gram import kruskal_norm_sq
            inner = jax.lax.psum(
                jnp.sum(jnp.sum(m2_blk * c_new, axis=0) * lam), all_ax)
            norm_z_sq = kruskal_norm_sq(lam, (ga, gb, gc))
            resid = jnp.maximum(norm_x_sq + norm_z_sq - 2.0 * inner, 0.0)
            fit = 1.0 - jnp.sqrt(resid) / jnp.sqrt(norm_x_sq)
            return a_new, b_new, c_new, lam, fit

        m2 = jax.lax.psum(m2, row_ax + (col_ax,))
        c_new = solve_cholesky(m2, v2)
        lam_c = column_norms(c_new, kind=norm_kind)
        safe = jnp.where(lam_c == 0.0, 1.0, lam_c)
        c_new, lam = c_new / safe[None, :], lam_c
        gc = gram(c_new)

        fit = kruskal_fit(norm_x_sq, lam, (ga, gb, gc), m2, c_new)
        return a_new, b_new, c_new, lam, fit

    smapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs)
    return jax.jit(smapped)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def dist_cp_als(t: SparseTensor, rank: int, mesh: Mesh, *, niters: int = 10,
                key: Array | None = None, verbose: bool = False,
                shard_c: bool = False, init: tuple | None = None,
                mode_order: str = "natural", monitor=None,
                impl: str = "auto", plan=None, method: str = "cp_als"):
    """Distributed CP-ALS; numerically equivalent to the shared-memory path
    (modulo f32 reduction order).  Returns (factors, lmbda, fit).

    ``mode_order='auto'``: partition the two LONGEST modes over the grid and
    exchange the SHORTEST (the mode-2 scatter/gather wire is proportional to
    its length) — EXPERIMENTS.md §Perf, cpals hillclimb.

    ``impl``/``plan``: the same planner interface as :func:`cp_als` —
    ``impl="auto"`` (default) measures per-mode statistics and picks each
    mode's local MTTKRP strategy (segment reduction for contention-heavy
    modes, scatter-add for collision-light ones); a concrete name pins all
    modes; a prebuilt :class:`repro.plan.DecompPlan` skips planning.  The
    candidate set is restricted to what the shard_map body can express
    (``gather_scatter``/``segment``).

    ``monitor``: an optional :class:`repro.dist.StragglerMonitor`; each ALS
    iteration's wall time is recorded for every participating host (times
    are exchanged across processes when there are several — see
    ``repro.dist.straggler.record_step_times``), so imbalance across the
    non-zero partition becomes visible at the driver.

    ``t`` may be a :class:`repro.ingest.Ingested` handle: planning reuses
    the ingest-time stats and the returned factors are mapped back to the
    original labels through the handle's inverse relabeling.

    ``method``: a name from the decomposition-method registry
    (``repro.methods``).  The shard_map body implements the CP-ALS update;
    methods whose :class:`~repro.methods.MethodSpec` declares
    ``supports_dist=False`` (sequential HALS column updates, chunk
    streaming, the Kronecker-width TTMc) are rejected with the capability
    listing instead of silently computing something else."""
    from .cpals import init_factors
    from repro.api.executor import require_capability

    # the one capability gate (repro.api.executor): same error text here,
    # in the dry-run, and in Session.fit(executor="dist")
    require_capability(method, "dist")

    ing = None
    if not isinstance(t, SparseTensor):
        from repro.ingest import Ingested

        if not isinstance(t, Ingested):
            raise TypeError(
                f"dist_cp_als takes a SparseTensor or repro.ingest.Ingested,"
                f" got {type(t).__name__}")
        ing = t
        t = ing.tensor
    if plan is None:
        if impl != "auto" and impl not in DIST_IMPLS:
            raise ValueError(
                f"dist_cp_als cannot execute impl {impl!r}: the shard_map "
                f"body expresses only {DIST_IMPLS} as local reductions")
        if ing is not None:
            plan = ing.plan(impl, rank=rank, allow=DIST_IMPLS)
        else:
            from repro.plan import plan_decomposition

            plan = plan_decomposition(t, impl, rank=rank, allow=DIST_IMPLS,
                                      with_stats=impl == "auto")
    elif not set(plan.impls) <= set(DIST_IMPLS):
        raise ValueError(
            f"dist_cp_als cannot execute plan {plan.summary()!r}: the "
            f"shard_map body expresses only {DIST_IMPLS} as local reductions")

    if mode_order == "auto":
        # longest modes over the grid, shortest on the wire (dims are always
        # available from the tensor — no dependency on plan stats)
        perm = tuple(sorted(range(3), key=lambda m: -t.dims[m]))
        tp = SparseTensor(inds=t.inds[:, list(perm)], vals=t.vals,
                          dims=tuple(t.dims[m] for m in perm), nnz=t.nnz)
        if init is not None:
            init = tuple(init[m] for m in perm)
        pplan = dataclasses.replace(plan, modes=tuple(
            dataclasses.replace(plan.modes[m], mode=pos)
            for pos, m in enumerate(perm)))
        factors, lam, fit = dist_cp_als(
            tp, rank, mesh, niters=niters, key=key, verbose=verbose,
            shard_c=shard_c, init=init, mode_order="natural",
            monitor=monitor, impl=impl, plan=pplan, method=method)
        inv = [0] * 3
        for pos, m in enumerate(perm):
            inv[m] = pos
        factors = tuple(factors[inv[m]] for m in range(3))
        if ing is not None:
            factors = ing.restore_factors(factors)
        return factors, lam, fit

    local_impls = _local_impls_of(plan)
    ax = cpals_axes(mesh)
    n_row, n_col, n_all = ax.n_row, ax.n_col, ax.n_all

    inds, vals, dims_p = partition_tensor(t, n_row, n_col)
    # place every input under the spec the iteration consumes it with, so
    # each device holds its own share from the start
    shard = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    inds = shard(inds, ax.grid_spec())
    vals = shard(vals, ax.grid_spec())
    i_p, j_p, k_dim = dims_p
    if shard_c:
        k_dim = -(-k_dim // n_all) * n_all
        dims_p = (i_p, j_p, k_dim)
    if key is None:
        key = jax.random.PRNGKey(0)
    if init is not None:
        full = tuple(
            jnp.zeros((dp, rank), t.vals.dtype).at[: f.shape[0]].set(f)
            for f, dp in zip(init, (i_p, j_p, k_dim)))
    else:
        full = init_factors((i_p, j_p, k_dim), rank, key, dtype=t.vals.dtype)
    # zero padded factor rows so grams match the unpadded computation
    a0 = shard(full[0].at[t.dims[0]:].set(0.0), ax.row_spec())
    b0 = shard(full[1].at[t.dims[1]:].set(0.0), ax.col_spec())
    c0 = shard(full[2].at[t.dims[2]:].set(0.0),
               ax.all_spec() if shard_c else P())
    norm_x_sq = jnp.sum(t.vals.astype(jnp.float32) ** 2)

    it_first = make_dist_iteration(mesh, dims_p, rank, norm_kind="max",
                                   shard_c=shard_c, local_impls=local_impls)
    it_rest = make_dist_iteration(mesh, dims_p, rank, norm_kind="2",
                                  shard_c=shard_c, local_impls=local_impls)

    a, b, c = a0, b0, c0
    lam = jnp.ones((rank,), dtype=t.vals.dtype)
    fit = jnp.array(0.0)
    traced = obs_trace.tracing()
    for i in range(niters):
        fn = it_first if i == 0 else it_rest
        t0 = time.time()
        with obs_trace.span("iteration", method="dist_cp_als", i=i):
            a, b, c, lam, fit = fn(inds, vals, a, b, c, norm_x_sq)
            if traced:
                jax.block_until_ready(fit)  # honest span duration
        if monitor is not None:
            from repro.dist.straggler import record_step_times
            jax.block_until_ready(fit)
            record_step_times(monitor, time.time() - t0)
            flags = monitor.check()
            if flags and verbose:
                print(f"  dist its={i + 1} stragglers: {flags}")
        if traced:
            from repro.obs.recorder import record_event

            record_event("dist.iteration", i=int(i), fit=float(fit),
                         ms=(time.time() - t0) * 1e3)
        if verbose:
            print(f"  dist its={i + 1} fit={float(fit):.6f}")
    factors = (a[: t.dims[0]], b[: t.dims[1]], c[: t.dims[2]])
    if ing is not None:
        factors = ing.restore_factors(factors)
    return factors, lam, fit


def build_dist_cpals_lowered(workload: str, mesh: Mesh, *,
                             shard_c: bool = False,
                             mode_order: str = "natural",
                             local_impls: tuple[str, str, str] = ("scatter",) * 3):
    """Abstract (ShapeDtypeStruct) lowering of one distributed ALS iteration
    for a paper workload — the CP-ALS entry of the dry-run matrix."""
    dims, nnz, _ = PAPER_DATASETS[PAPER_WORKLOADS[workload]]
    rank = PAPER_RANK
    if mode_order == "auto":
        dims = tuple(sorted(dims, reverse=True))
    ax = cpals_axes(mesh)
    row_ax = ax.row
    n_row, n_col, n_all = ax.n_row, ax.n_col, ax.n_all
    i_p = -(-dims[0] // n_row) * n_row
    j_p = -(-dims[1] // n_col) * n_col
    cap = int(np.ceil(nnz / (n_row * n_col) * 1.2))
    k_p = -(-dims[2] // n_all) * n_all if shard_c else dims[2]
    dims_p = (i_p, j_p, k_p)

    sds = jax.ShapeDtypeStruct
    sh = lambda spec: NamedSharding(mesh, spec)
    inds = sds((n_row, n_col, cap, 3), jnp.int32, sharding=sh(ax.grid_spec()))
    vals = sds((n_row, n_col, cap), jnp.float32, sharding=sh(ax.grid_spec()))
    a = sds((i_p, rank), jnp.float32, sharding=sh(ax.row_spec()))
    b = sds((j_p, rank), jnp.float32, sharding=sh(ax.col_spec()))
    c_spec = ax.all_spec() if shard_c else P()
    c = sds((k_p, rank), jnp.float32, sharding=sh(c_spec))
    nx = sds((), jnp.float32)

    fn = make_dist_iteration(mesh, dims_p, rank, shard_c=shard_c,
                             local_impls=local_impls)
    lowered = fn.lower(inds, vals, a, b, c, nx)
    # MTTKRP flops: ~5 R nnz per mode (2R gather-products, R scatter-add,
    # 2R for the Khatri-Rao partial) x 3 modes, plus small dense terms.
    info = {"workload": workload, "dims": dims, "nnz": nnz, "rank": rank,
            "local_cap": cap, "shard_c": shard_c, "mode_order": mode_order,
            "local_impls": list(local_impls),
            "model_flops": 3 * 5.0 * rank * nnz}
    return lowered, info
