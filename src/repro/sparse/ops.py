"""CSR primitives (jit-compatible, static capacities).

These are the substrate ops the paper's applications are built on:
Markov Clustering needs column normalization, Hadamard powers and top-k
column pruning (Algorithm 6); Graph Contraction needs transposes
(Algorithm 7); GNNs need SpMM.  All ops preserve the static capacity of
their inputs so they compose under ``jit``/``scan``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.sparse.formats import CSR


def csr_row_nnz(a: CSR) -> jax.Array:
    return a.row_nnz()


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_rows(x: jax.Array, idx: jax.Array, gather: str = "xla") -> jax.Array:
    """rows_of_x = x[idx] with a pluggable gather backend.

    ``gather="aia"`` serves the indirection through the scalar-prefetch
    Pallas kernels (``kernels.aia_gather``, backend auto-detected); the
    backward pass is always an XLA scatter-add, so the op stays
    differentiable either way (Pallas kernels have no AD rules).
    """
    if gather == "aia":
        from repro.kernels.aia_gather import gather_rows_any
        return gather_rows_any(x, idx)
    return jnp.take(x, idx, axis=0, mode="clip")


def _take_rows_fwd(x, idx, gather):
    return _take_rows(x, idx, gather), (idx, x.shape[0])


def _take_rows_bwd(gather, res, ct):
    idx, n = res
    safe = jnp.clip(idx, 0, n - 1)
    return jnp.zeros((n, ct.shape[1]), ct.dtype).at[safe].add(ct), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def csr_transpose(a: CSR, capacity: int | None = None) -> CSR:
    """CSR transpose via stable sort on column ids (jit-compatible).

    Padding slots sort to the end because their key is ``n_cols``.
    """
    cap = capacity if capacity is not None else a.capacity
    valid = a.valid_mask()
    key = jnp.where(valid, a.indices, a.n_cols)
    order = jnp.argsort(key, stable=True)
    new_rows = key[order]  # transposed row id per slot (n_cols for padding)
    rid = a.row_ids()  # original row = transposed col
    new_cols = jnp.where(valid, rid, 0)[order]
    new_data = jnp.where(valid, a.data, 0)[order]
    counts = jnp.zeros(a.n_cols + 1, jnp.int32).at[new_rows].add(
        valid[order].astype(jnp.int32)
    )[: a.n_cols]
    indptr = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)]).astype(jnp.int32)
    if cap == a.capacity:
        indices, data = new_cols, new_data
    elif cap > a.capacity:
        indices = jnp.zeros(cap, jnp.int32).at[: a.capacity].set(new_cols)
        data = jnp.zeros(cap, a.data.dtype).at[: a.capacity].set(new_data)
    else:
        indices, data = new_cols[:cap], new_data[:cap]
    return CSR(indptr, indices, data, (a.n_cols, a.n_rows))


def csr_spmv(a: CSR, x: jax.Array) -> jax.Array:
    """y = A @ x for dense vector x: gather + segment-sum."""
    valid = a.valid_mask()
    contrib = jnp.where(valid, a.data * jnp.take(x, a.indices, mode="clip"), 0)
    rid = a.row_ids()
    return jnp.zeros(a.n_rows + 1, contrib.dtype).at[rid].add(contrib)[: a.n_rows]


# Bytes of padded gathered rows one aggregation chunk may hold: the smallest
# power of two at which the ogbn-arxiv GCN's widest aggregation (2.38 GB)
# stays a single chunk.
SPMM_CHUNK_BYTES = 4 << 30


def spmm_chunks(capacity: int, d: int) -> tuple[int, int]:
    """(chunks, entries per chunk) of ``csr_spmm`` over ``capacity`` stored
    entries and rows of width ``d``.

    A chunk gathers at most ``SPMM_CHUNK_BYTES`` of rows padded to the
    128-lane tile a row DMA moves (``padded_width(d)`` 32-bit words each),
    whatever the gather backend, so the split is the same on every
    backend.  Chunks are equal, a multiple of the AIA gather's index block
    where the budget allows; the last one is padded with invalid slots.
    """
    from repro.kernels.aia_gather import IDX_BLOCK, padded_width

    per = max(SPMM_CHUNK_BYTES // (padded_width(d) * 4), 1)
    if capacity <= per:
        return 1, capacity
    unit = IDX_BLOCK if per >= IDX_BLOCK else 1
    units = -(-capacity // unit)
    n = -(-units // (per // unit))
    return n, -(-units // n) * unit


def csr_spmm(a: CSR, x: jax.Array, gather: str = "xla", mesh=None) -> jax.Array:
    """Y = A @ X for dense X (n_cols, d): the GNN aggregation primitive.

    This is the *two-level indirect access* the paper targets: ``indices``
    selects rows of ``X`` (ranged access of length d), results are
    segment-summed by row.  ``gather="aia"`` serves that gather with the
    scalar-prefetch Pallas kernels (Fig. 7 ablation); ``"auto"`` picks AIA
    on TPU and XLA elsewhere.  ``mesh`` constrains the output to be
    row-sharded over the mesh's first axis so GSPMD partitions the gather +
    segment-sum across devices (jit-safe: constraint only, no placement).

    The stored entries are split by ``spmm_chunks`` so that no pass holds
    more than ``SPMM_CHUNK_BYTES`` of gathered rows: each chunk is gathered,
    scaled and segment-summed into the output in turn, in one ``scan``,
    and the backward scatter-add runs per chunk too.  One chunk is the
    plain gather → scale → segment-sum.  Every op, forward and transposed,
    carries the name scope ``csr_spmm``.
    """
    from repro.core.executor import resolve_gather  # lazy: avoids pkg cycle
    gather = resolve_gather(gather)  # validates + honors REPRO_KERNEL_BACKEND
    with jax.named_scope("csr_spmm"):
        n_chunks, chunk = spmm_chunks(a.capacity, x.shape[1])
        if n_chunks == 1:
            valid = a.valid_mask()
            rows_of_x = _take_rows(x, a.indices, gather)  # (cap, d)
            contrib = jnp.where(valid[:, None], a.data[:, None] * rows_of_x, 0)
            rid = a.row_ids()
            out = jnp.zeros((a.n_rows + 1, x.shape[1]), contrib.dtype).at[rid].add(contrib)
        else:
            pad = n_chunks * chunk - a.capacity
            xs = (
                jnp.pad(a.indices, (0, pad)).reshape(n_chunks, chunk),
                jnp.pad(a.data, (0, pad)).reshape(n_chunks, chunk),
                jnp.arange(n_chunks, dtype=jnp.int32) * chunk,
            )

            def step(out, xs):
                indices, data, start = xs
                rid = a.row_ids(start, chunk)  # n_rows on padding slots
                # The mask scales the entries, not the gathered rows: its
                # (chunk, d) broadcast would be kept for the backward pass.
                scale = jnp.where(rid < a.n_rows, data, 0)
                rows_of_x = _take_rows(x, indices, gather)  # (chunk, d)
                return out.at[rid].add(scale[:, None] * rows_of_x), None

            dtype = jnp.result_type(a.data.dtype, x.dtype)
            out = jnp.zeros((a.n_rows + 1, x.shape[1]), dtype)
            out, _ = jax.lax.scan(step, out, xs)
        out = out[: a.n_rows]
        if mesh is not None:
            from repro.launch.sharding import row_sharding
            out = jax.lax.with_sharding_constraint(out, row_sharding(mesh, out.ndim))
    return out


def csr_scale_rows(a: CSR, s: jax.Array) -> CSR:
    """diag(s) @ A."""
    rid = a.row_ids()
    sv = jnp.take(s, jnp.clip(rid, 0, a.n_rows - 1), mode="clip")
    return CSR(a.indptr, a.indices, jnp.where(a.valid_mask(), a.data * sv, 0), a.shape)


def csr_scale_columns(a: CSR, s: jax.Array) -> CSR:
    """A @ diag(s)."""
    sv = jnp.take(s, a.indices, mode="clip")
    return CSR(a.indptr, a.indices, jnp.where(a.valid_mask(), a.data * sv, 0), a.shape)


def csr_hadamard_power(a: CSR, r: float) -> CSR:
    """Elementwise power on stored entries (MCL inflation, Alg. 6 line 12)."""
    valid = a.valid_mask()
    d = jnp.where(valid, a.data, 1.0)
    return CSR(a.indptr, a.indices, jnp.where(valid, jnp.power(d, r), 0), a.shape)


def csr_column_sums(a: CSR) -> jax.Array:
    valid = a.valid_mask()
    return jnp.zeros(a.n_cols, a.data.dtype).at[a.indices].add(
        jnp.where(valid, a.data, 0)
    )


def csr_column_normalize(a: CSR, eps: float = 1e-12) -> CSR:
    """Make columns sum to one (MCL's ColumnNormalize)."""
    s = csr_column_sums(a)
    inv = jnp.where(s > eps, 1.0 / jnp.maximum(s, eps), 0.0)
    return csr_scale_columns(a, inv)


def csr_prune_columns(a: CSR, theta: float, k: int) -> CSR:
    """MCL Prune (Alg. 6 lines 6–10): drop entries < theta, keep top-k per column.

    Keeps the CSR layout (entries are zeroed in place, structure retained) —
    the *values* become exactly the pruned matrix; callers needing compaction
    re-build via ``ell_to_csr``/host utilities.
    """
    valid = a.valid_mask()
    vals = jnp.where(valid, a.data, 0)
    vals = jnp.where(vals >= theta, vals, 0)
    # top-k per column with a fixed number of sort passes:
    # rank entries within each column by value (descending) via sort on
    # (col, -val); entries with per-column rank >= k are dropped.
    col_key = jnp.where(valid, a.indices, a.n_cols)
    order = jnp.lexsort((-vals, col_key))  # sort by col, then value desc
    sorted_cols = col_key[order]
    # rank within column = position - first position of this column
    pos = jnp.arange(a.capacity)
    is_start = jnp.concatenate([jnp.ones(1, bool), sorted_cols[1:] != sorted_cols[:-1]])
    start_pos = jnp.where(is_start, pos, 0)
    start_pos = jax.lax.associative_scan(jnp.maximum, start_pos)
    rank = pos - start_pos
    keep_sorted = rank < k
    keep = jnp.zeros(a.capacity, bool).at[order].set(keep_sorted)
    new_data = jnp.where(keep, vals, 0)
    return CSR(a.indptr, a.indices, new_data, a.shape)


def csr_permute_rows(a: CSR, perm: jax.Array, inverse: bool = False) -> CSR:
    """Reorder rows by ``perm`` (Map from the paper's row-grouping phase).

    ``perm[i]`` = original row id placed at new position i.  Only the
    *logical* order changes; used to build locality-friendly schedules.
    """
    if inverse:
        perm = jnp.argsort(perm)
    counts = a.row_nnz()[perm]
    new_indptr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)]
    ).astype(jnp.int32)
    # scatter each old slot to its new flat position
    old_starts = a.indptr[:-1][perm]  # start of source row for each new row
    k_cap = a.capacity
    # build via gather: for each new flat slot, find its (new_row, within) and
    # read from old_starts[new_row] + within.
    p = jnp.arange(k_cap, dtype=jnp.int32)
    new_rid = jnp.searchsorted(new_indptr, p, side="right").astype(jnp.int32) - 1
    valid = p < new_indptr[-1]
    new_rid_c = jnp.clip(new_rid, 0, a.n_rows - 1)
    within = p - new_indptr[new_rid_c]
    src = jnp.take(old_starts, new_rid_c, mode="clip") + within
    src = jnp.where(valid, src, 0)
    indices = jnp.where(valid, a.indices[src], 0)
    data = jnp.where(valid, a.data[src], 0)
    return CSR(new_indptr, indices, data, a.shape)
