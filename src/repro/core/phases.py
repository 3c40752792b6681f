"""Allocation + accumulation phase engines (paper Algorithms 2/3/5).

Each engine consumes one *group* of rows (from the row-grouping phase) with
static shapes: ``a_cap`` = max nnz(A-row) in the group, ``kb_cap`` = max
nnz(B-row) globally, ``table_cap`` = the group's Table-I hash capacity.

Two interchangeable engines, validated against each other and the dense
oracle:

* ``*_hash``  — faithful Algorithm 4 semantics (linear-probing table per
  row, sequential insert stream, vmapped across rows = the paper's
  PWPR/TBPR across-row parallelism).
* ``*_sort``  — the TPU-vectorized engine (Nagasaka-style sort+segment-sum);
  same results, MXU/VPU-friendly, used for large scale and inside jitted
  training graphs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hashtable as ht

INT_MAX = jnp.int32(2**31 - 1)


# ---------------------------------------------------------------------------
# Intermediate-product enumeration (the two-level indirection itself)
# ---------------------------------------------------------------------------

def stream_width(ip_cap: int) -> int:
    """Width of a padded intermediate-product stream of ``ip_cap`` products:
    the next power of two up to 1024, a multiple of 1024 beyond.

    The TPU compiler takes tens of seconds over the accumulate programs'
    scatters and sorts at widths such as 2955 (``a_cap·kb_cap`` of a
    power-law B) and about a second at these widths; the padding slots are
    ``-1`` keys, which every engine skips, so results do not change."""
    ip_cap = max(int(ip_cap), 1)
    if ip_cap <= 1024:
        return 1 << (ip_cap - 1).bit_length()
    return -(-ip_cap // 1024) * 1024


def _pad_stream(x, width: int, fill):
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                   constant_values=fill)


def combine_products(cols_a, vals_a, bi, bv):
    """Form intermediate products from already-gathered B rows.

    cols_a, vals_a: (R, a_cap) padded with -1 / 0 — the rows' A entries.
    bi, bv:         (R, a_cap, kb) the gathered B rows ``b_idx[cols_a]`` /
                    ``b_val[cols_a]`` (any gather backend; padding rows may
                    hold garbage — they are masked by ``cols_a < 0``).
    Returns keys (R, stream_width(a_cap*kb)) int32 (-1 padded) and vals
    (same shape).
    """
    r, a_cap = cols_a.shape
    kb = bi.shape[2]
    w = stream_width(a_cap * kb)
    valid = (cols_a >= 0)[:, :, None] & (bi >= 0)
    keys = jnp.where(valid, bi, -1).reshape(r, a_cap * kb)
    vals = jnp.where(valid, vals_a[:, :, None] * bv, 0).reshape(r, a_cap * kb)
    return _pad_stream(keys, w, -1), _pad_stream(vals, w, 0)


def combine_products_batched(cols_a, vals_a_b, bi, bv_b):
    """``combine_products`` over a leading batch of same-pattern values.

    The sparsity pattern (and therefore ``keys``) is shared across the
    batch; only the values carry the batch axis, so the key tensor is
    computed once and the product values broadcast over it.

    cols_a: (R, a_cap) shared structure; vals_a_b: (B, R, a_cap).
    bi: (R, a_cap, kb) shared gathered B structure; bv_b: (B, R, a_cap, kb).
    Returns keys (R, W) and vals (B, R, W), W = stream_width(a_cap*kb).
    """
    r, a_cap = cols_a.shape
    kb = bi.shape[2]
    batch = vals_a_b.shape[0]
    w = stream_width(a_cap * kb)
    valid = (cols_a >= 0)[:, :, None] & (bi >= 0)
    keys = jnp.where(valid, bi, -1).reshape(r, a_cap * kb)
    vals = jnp.where(valid[None], vals_a_b[:, :, :, None] * bv_b, 0)
    return (_pad_stream(keys, w, -1),
            _pad_stream(vals.reshape(batch, r, a_cap * kb), w, 0))


def enumerate_products(cols_a, vals_a, b_idx, b_val):
    """Per-row intermediate products (XLA-gather variant).

    cols_a, vals_a: (R, a_cap) padded with -1 / 0 — the rows' A entries.
    b_idx, b_val:  (nB, kb_cap) ELL of B.
    Returns keys (R, stream_width(a_cap*kb_cap)) int32 (-1 padded) and
    vals (same shape).

    ``b_idx[cols_a]`` is exactly the AIA ranged indirect access
    (``rpt_B[col_A[j]]`` → row of B); here expressed as an XLA gather, in
    ``repro.kernels.aia_gather`` as an SMEM-indexed DMA stream (selected
    via the executor's ``gather=`` knob).
    """
    safe = jnp.clip(cols_a, 0, b_idx.shape[0] - 1)
    bi = b_idx[safe]  # (R, a_cap, kb)
    bv = b_val[safe]
    return combine_products(cols_a, vals_a, bi, bv)


def remap_columns(cols, remap):
    """Translate global A-column ids to block-local B-row ids.

    ``remap`` is the footprint block's (n_rows(B),) int32 map — ``-1`` for
    rows absent from the block.  Padding entries (``cols < 0``) stay ``-1``,
    and a valid column that the block does not hold also maps to ``-1``, so
    downstream masking (``combine_products``'s ``cols_a >= 0``) drops it
    instead of gathering garbage — by construction a shard's own work items
    never produce such a column, but the guarantee keeps the remapped
    gather safe under any footprint.
    """
    safe = jnp.clip(cols, 0, remap.shape[0] - 1)
    return jnp.where(cols >= 0, remap[safe], -1)


def gather_group_rows(indptr, indices, data, rows, a_cap):
    """Gather the A entries of ``rows`` (padded with -1) into (R, a_cap)."""
    n_rows = indptr.shape[0] - 1
    safe_rows = jnp.clip(rows, 0, n_rows - 1)
    starts = indptr[safe_rows]  # (R,)
    counts = indptr[safe_rows + 1] - starts
    offs = jnp.arange(a_cap, dtype=jnp.int32)[None, :]
    pos = starts[:, None] + offs
    ok = (offs < counts[:, None]) & (rows >= 0)[:, None]
    pos = jnp.where(ok, pos, 0)
    cols = jnp.where(ok, indices[pos], -1)
    vals = jnp.where(ok, data[pos], 0)
    return cols, vals


def gather_group_rows_batched(indptr, indices, data_b, rows, a_cap):
    """``gather_group_rows`` with a leading batch of value sets.

    The CSR structure (indptr/indices) is shared; ``data_b`` is (B, cap).
    Returns (cols (R, a_cap), vals (B, R, a_cap)) — one structural gather
    serving every batch member.
    """
    n_rows = indptr.shape[0] - 1
    safe_rows = jnp.clip(rows, 0, n_rows - 1)
    starts = indptr[safe_rows]  # (R,)
    counts = indptr[safe_rows + 1] - starts
    offs = jnp.arange(a_cap, dtype=jnp.int32)[None, :]
    pos = starts[:, None] + offs
    ok = (offs < counts[:, None]) & (rows >= 0)[:, None]
    pos = jnp.where(ok, pos, 0)
    cols = jnp.where(ok, indices[pos], -1)
    vals = jnp.where(ok[None], data_b[:, pos], 0)  # (B, R, a_cap)
    return cols, vals


# ---------------------------------------------------------------------------
# Fused single-pass hash accumulation (the ``fused_hash`` engine core)
# ---------------------------------------------------------------------------

def fused_hash_sorted(keys, vals, table_cap: int, out_cap: int,
                      kernel: str = "xla"):
    """Algorithms 2/3/5 in one pass: the intermediate-product stream is
    inserted straight into the per-row linear-probing table and the sorted,
    ``out_cap``-trimmed output comes back — no separate allocate pass, so
    the caller must size ``out_cap`` from an a-priori bound (the plan's
    Alg. 1 IP counts guarantee uniqueCount ≤ min(IP, n_cols) per row).

    ``kernel`` routes Algorithm 4: ``"pallas"``/``"interpret"`` use the
    Pallas TPU kernel (``kernels.hash_accum``, unsorted table + occupancy;
    column sorting stays in XLA per the paper's phase split); ``"xla"`` is
    the vmapped sequential-scan engine.  Both consume the stream in the
    same order, so results are bit-identical to the two-pass hash engine.
    """
    if kernel in ("pallas", "interpret"):
        from repro.kernels.hash_accum import hash_accumulate_sorted

        return hash_accumulate_sorted(keys, vals, table_cap, out_cap,
                                      interpret=(kernel == "interpret"))
    cols, out_vals, counts = accumulate_hash(keys, vals, table_cap)
    return cols[:, :out_cap], out_vals[:, :out_cap], counts


# ---------------------------------------------------------------------------
# Device-side CSR reassembly epilogue (inverse-permutation scatter on device)
# ---------------------------------------------------------------------------

# Largest scatter operand (elements) the epilogue writes in one piece.  The
# TPU compiler spends seconds on a scatter into a buffer of 2**22 or more
# elements and a fraction of one below that, so larger buffers are
# scattered piece by piece and joined.
SCATTER_PIECE = 1 << 21


def scatter_drop(buf, pos, upd):
    """``buf.at[..., pos].set(upd, mode="drop")`` along the last axis.

    ``pos`` must be non-negative; positions at or past the buffer's length
    are dropped.  A buffer larger than ``SCATTER_PIECE`` elements is
    written in pieces of the last axis, each piece taking the positions
    that fall inside it (the rest are redirected past its end), so every
    scatter the compiler sees stays small."""
    cap = buf.shape[-1]
    lead = buf.size // max(cap, 1)
    piece = max(SCATTER_PIECE // max(lead, 1), 1024)
    if cap <= piece:
        return buf.at[..., pos].set(upd, mode="drop")
    parts = []
    for base in range(0, cap, piece):
        size = min(piece, cap - base)
        loc = jnp.where((pos >= base) & (pos < base + size), pos - base, size)
        parts.append(buf[..., base:base + size].at[..., loc].set(
            upd, mode="drop"))
    return jnp.concatenate(parts, axis=-1)


def _scatter_pos(counts, starts, out_cap, sentinel):
    """Flat destinations of one chunk's (row, slot) cells: ``starts + offs``
    where the slot is occupied, the out-of-range ``sentinel`` (dropped by
    ``mode="drop"`` scatters) where it is not — the one masking convention
    shared by the direct and sharded, single and batched epilogues."""
    offs = jnp.arange(out_cap, dtype=jnp.int32)[None, :]
    return jnp.where(offs < counts[:, None], starts[:, None] + offs, sentinel)


def reassemble_device(idx_buf, dat_buf, cols, vals, counts, starts):
    """Scatter one chunk's accumulated rows into the final CSR buffers.

    The device-side half of CSR reassembly: each chunk's column-sorted rows
    are written to their flat CSR destinations with one vectorized scatter,
    so the output ``indices``/``data`` never round-trip through NumPy.

    idx_buf, dat_buf: (cap,) int32 / dtype — the output CSR's index and
                      value buffers (functionally updated and returned).
    cols, vals:       (R_pad, out_cap) the chunk's accumulated rows.
    counts:           (R_pad,) int32 per-row occupancy; padding rows are 0.
    starts:           (R_pad,) int32 CSR start offset of each row.

    Everything stays int32 (the CSR index convention); positions past a
    row's count are redirected to ``cap`` and dropped by the scatter, which
    also silently retires padding rows (count 0).
    """
    pos = _scatter_pos(counts, starts, cols.shape[1], idx_buf.shape[0])
    return scatter_drop(idx_buf, pos, cols), scatter_drop(dat_buf, pos, vals)


def reassemble_device_batched(idx_buf, dat_buf_b, cols, vals_b, counts, starts):
    """``reassemble_device`` with the value scatter broadcast over a batch.

    The output structure is shared by every batch member, so the position
    tensor is computed once; ``dat_buf_b`` is (batch, cap) and ``vals_b``
    (batch, R_pad, out_cap).
    """
    pos = _scatter_pos(counts, starts, cols.shape[1], idx_buf.shape[0])
    return scatter_drop(idx_buf, pos, cols), scatter_drop(dat_buf_b, pos,
                                                          vals_b)


# ---------------------------------------------------------------------------
# Sharded epilogue: shard-local CSR segments + destination-mapped merge
# ---------------------------------------------------------------------------

def _exclusive_cumsum(x):
    return jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(x)[:-1]])


def reassemble_segment(seg_idx, seg_dat, dest, off, cols, vals, counts,
                       fin_starts):
    """Shard-local half of the sharded epilogue: pack one chunk's rows
    *densely* into the shard's segment buffers and record each slot's
    destination in the final CSR buffers.

    The shard device does its own reassembly scatter (in parallel with the
    other shards) and the merge device later receives one compact
    ``(segment, dest)`` pair per shard instead of every padded chunk
    output — the merge traffic that used to flow through the lead device
    per chunk stays shard-local until the final per-shard merge.

    seg_idx, seg_dat: (seg_cap,) the shard's local segment buffers.
    dest:             (seg_cap,) int32 final-buffer position per segment
                      slot; unused slots keep their init sentinel (the
                      final capacity), which the merge scatter drops.
    off:              () int32 running shard-local offset (nnz packed so
                      far); threaded through chunk after chunk.
    cols, vals:       (R_pad, out_cap) the chunk's accumulated rows.
    counts:           (R_pad,) int32 per-row occupancy (padding rows 0).
    fin_starts:       (R_pad,) int32 final CSR start offset of each row.
    """
    out_cap = cols.shape[1]
    loc_starts = off + _exclusive_cumsum(counts)
    pos = _scatter_pos(counts, loc_starts, out_cap, seg_idx.shape[0])
    offs = jnp.arange(out_cap, dtype=jnp.int32)[None, :]
    seg_idx = scatter_drop(seg_idx, pos, cols)
    seg_dat = scatter_drop(seg_dat, pos, vals)
    dest = scatter_drop(dest, pos, fin_starts[:, None] + offs)
    return seg_idx, seg_dat, dest, off + jnp.sum(counts)


def reassemble_segment_batched(seg_idx, seg_dat_b, dest, off, cols, vals_b,
                               counts, fin_starts):
    """``reassemble_segment`` with the value packing broadcast over a
    batch: ``seg_dat_b`` is (batch, seg_cap), ``vals_b`` (batch, R_pad,
    out_cap); structure (cols/counts/positions) is shared by every
    member."""
    out_cap = cols.shape[1]
    loc_starts = off + _exclusive_cumsum(counts)
    pos = _scatter_pos(counts, loc_starts, out_cap, seg_idx.shape[0])
    offs = jnp.arange(out_cap, dtype=jnp.int32)[None, :]
    seg_idx = scatter_drop(seg_idx, pos, cols)
    seg_dat_b = scatter_drop(seg_dat_b, pos, vals_b)
    dest = scatter_drop(dest, pos, fin_starts[:, None] + offs)
    return seg_idx, seg_dat_b, dest, off + jnp.sum(counts)


def merge_segments(idx_buf, dat_buf, seg_idx, seg_dat, dest):
    """Merge one shard's packed segment into the final CSR buffers: a
    single destination-mapped scatter per shard (unused segment slots
    carry the out-of-range sentinel and are dropped)."""
    return (scatter_drop(idx_buf, dest, seg_idx),
            scatter_drop(dat_buf, dest, seg_dat))


def merge_segments_batched(idx_buf, dat_buf_b, seg_idx, seg_dat_b, dest):
    return (scatter_drop(idx_buf, dest, seg_idx),
            scatter_drop(dat_buf_b, dest, seg_dat_b))


def merge_segments_host(idx_buf, dat_buf, seg_idx, seg_dat, dest):
    """``merge_segments`` for host-resident final buffers — the streamed
    lane's inter-tile epilogue.

    A completed tile comes back as a compact CSR segment exactly like a
    shard's packed segment, and merges the same way: one destination-mapped
    scatter into the final ``indices``/``data`` buffers (a tile is just
    another segment).  Same sentinel convention as the device merge —
    positions at/past the buffer capacity are dropped — but NumPy in-place
    on the host, where the streamed lane accumulates the out-of-core
    result.  Mutates and returns ``idx_buf``/``dat_buf``.
    """
    keep = dest < idx_buf.shape[0]
    idx_buf[dest[keep]] = seg_idx[keep]
    dat_buf[dest[keep]] = seg_dat[keep]
    return idx_buf, dat_buf


# ---------------------------------------------------------------------------
# Hash engine (Algorithm 2/3 allocation; Algorithm 5 accumulation)
# ---------------------------------------------------------------------------

def _row_alloc_hash(keys, table_cap):
    tab = ht.make_table(table_cap)
    tab = ht.insert_stream(tab, keys, jnp.zeros_like(keys, jnp.float32),
                           accumulate=False)
    return tab.count


def _row_accum_hash(keys, vals, table_cap):
    tab = ht.make_table(table_cap, vals.dtype)
    tab = ht.insert_stream(tab, keys, vals, accumulate=True)
    return ht.extract_sorted(tab)


@functools.partial(jax.jit, static_argnames=("table_cap",))
def allocate_hash(keys, table_cap: int):
    """uniqueCount per row (Algorithms 2/3 output).  keys: (R, ip_cap)."""
    return jax.vmap(lambda k: _row_alloc_hash(k, table_cap))(keys)


@functools.partial(jax.jit, static_argnames=("table_cap",))
def accumulate_hash(keys, vals, table_cap: int):
    """(cols, vals, counts) per row, column-sorted (Algorithm 5 output)."""
    return jax.vmap(lambda k, v: _row_accum_hash(k, v, table_cap))(keys, vals)


# ---------------------------------------------------------------------------
# Sort engine (vectorized; identical outputs)
# ---------------------------------------------------------------------------

def sort_unique(keys, vals, out_cap):
    """Per-batch sort + segment-sum + compaction.  keys: (R, ip_cap).

    Public API of the sort engine (used by the executor registry and the
    fully-jitted ``spgemm_ell_fixed``); returns (cols, vals, counts) with
    column-sorted rows padded to ``out_cap``.
    """
    r, ip_cap = keys.shape
    skey = jnp.where(keys >= 0, keys, INT_MAX)
    order = jnp.argsort(skey, axis=1, stable=True)
    sk = jnp.take_along_axis(skey, order, axis=1)
    sv = jnp.take_along_axis(vals, order, axis=1)
    valid = sk != INT_MAX
    is_start = jnp.concatenate(
        [jnp.ones((r, 1), bool), sk[:, 1:] != sk[:, :-1]], axis=1
    ) & valid
    ur = jnp.cumsum(is_start, axis=1) - 1  # unique rank per slot
    counts = jnp.max(jnp.where(valid, ur + 1, 0), axis=1).astype(jnp.int32)
    rows_ix = jnp.arange(r)[:, None]
    tgt = jnp.where(valid & (ur < out_cap), ur, out_cap)
    out_vals = jnp.zeros((r, out_cap + 1), vals.dtype).at[rows_ix, tgt].add(
        jnp.where(valid, sv, 0)
    )[:, :out_cap]
    start_tgt = jnp.where(is_start & (ur < out_cap), ur, out_cap)
    out_cols = jnp.full((r, out_cap + 1), -1, jnp.int32).at[rows_ix, start_tgt].set(
        jnp.where(is_start, sk, -1).astype(jnp.int32)
    )[:, :out_cap]
    return out_cols, out_vals, counts


@jax.jit
def allocate_sort(keys):
    """uniqueCount per row via sort (no value accumulation)."""
    r, ip_cap = keys.shape
    skey = jnp.where(keys >= 0, keys, INT_MAX)
    sk = jnp.sort(skey, axis=1)
    valid = sk != INT_MAX
    is_start = jnp.concatenate(
        [jnp.ones((r, 1), bool), sk[:, 1:] != sk[:, :-1]], axis=1
    ) & valid
    return jnp.sum(is_start, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("out_cap",))
def accumulate_sort(keys, vals, out_cap: int):
    return sort_unique(keys, vals, out_cap)
