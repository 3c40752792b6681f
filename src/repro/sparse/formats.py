"""Sparse matrix formats with static (padded) capacities.

JAX requires static shapes under ``jit``; every format therefore separates
the *capacity* (static, shape-defining) from the *occupancy* (dynamic, data).

Conventions
-----------
* ``CSR``: ``indptr[(n_rows+1,)] int32``; ``indices[(cap,)] int32`` and
  ``data[(cap,)]`` padded beyond ``indptr[-1]`` with ``indices = 0`` and
  ``data = 0``.  Validity of slot ``p`` is ``p < indptr[-1]``; row ids are
  recovered with ``row_ids()``.
* ``ELL``: ``indices[(n_rows, k_cap)]`` padded with ``-1``;
  ``data[(n_rows, k_cap)]`` padded with ``0``.  Per-row occupancy is
  ``(indices >= 0).sum(-1)``.
* ``BSR``: block-CSR; ``indptr[(n_brows+1,)]``, ``indices[(bcap,)]`` block
  column ids, ``blocks[(bcap, bs_r, bs_c)]``.
* ``TopKRows``: the paper's Eq. (2) sparsified activation — exactly ``k``
  entries per row (``values[(n, k)]``, ``indices[(n, k)]``), no padding.

All containers are registered pytrees: array fields are leaves, the logical
``shape`` is static aux data, so they pass through ``jit``/``vmap``/``scan``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _register(cls):
    fields = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("static")]
    static = [f.name for f in dataclasses.fields(cls) if f.metadata.get("static")]

    def flatten(x):
        return tuple(getattr(x, n) for n in fields), tuple(getattr(x, n) for n in static)

    def unflatten(aux, leaves):
        return cls(**dict(zip(fields, leaves)), **dict(zip(static, aux)))

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


def _static(**kw):
    return dataclasses.field(metadata={"static": True}, **kw)


@_register
@dataclasses.dataclass(frozen=True)
class CSR:
    """Compressed sparse row with static capacity ``indices.shape[0]``."""

    indptr: jax.Array
    indices: jax.Array
    data: jax.Array
    shape: Tuple[int, int] = _static()

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def capacity(self) -> int:
        return self.indices.shape[0]

    @property
    def nnz(self) -> jax.Array:
        """Dynamic occupancy (a traced scalar under jit)."""
        return self.indptr[-1]

    def row_ids(self, start=None, size: int | None = None) -> jax.Array:
        """Row id of every slot (capacity,), or of the ``size`` slots from
        slot ``start`` on; padding slots (and slots past the capacity) get
        ``n_rows``."""
        p = jnp.arange(self.capacity if size is None else size, dtype=jnp.int32)
        if start is not None:
            p = p + start
        rid = jnp.searchsorted(self.indptr, p, side="right").astype(jnp.int32) - 1
        return jnp.where(p < self.nnz, rid, self.n_rows)

    def valid_mask(self) -> jax.Array:
        return jnp.arange(self.capacity) < self.nnz

    def row_nnz(self) -> jax.Array:
        return (self.indptr[1:] - self.indptr[:-1]).astype(jnp.int32)


@_register
@dataclasses.dataclass(frozen=True)
class ELL:
    """Padded row-major sparse rows: fixed ``k_cap`` slots per row."""

    indices: jax.Array  # (n_rows, k_cap) int32, -1 padded
    data: jax.Array  # (n_rows, k_cap)
    shape: Tuple[int, int] = _static()

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def k_cap(self) -> int:
        return self.indices.shape[1]

    def valid_mask(self) -> jax.Array:
        return self.indices >= 0

    def row_nnz(self) -> jax.Array:
        return self.valid_mask().sum(-1).astype(jnp.int32)


@_register
@dataclasses.dataclass(frozen=True)
class BSR:
    """Block-CSR with dense ``(bs_r, bs_c)`` blocks (MXU-aligned on TPU)."""

    indptr: jax.Array  # (n_brows + 1,)
    indices: jax.Array  # (bcap,) block-column ids, 0-padded
    blocks: jax.Array  # (bcap, bs_r, bs_c)
    shape: Tuple[int, int] = _static()  # element shape (rows, cols)

    @property
    def block_shape(self) -> Tuple[int, int]:
        return (self.blocks.shape[1], self.blocks.shape[2])

    @property
    def n_brows(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def n_bcols(self) -> int:
        return self.shape[1] // self.blocks.shape[2]

    @property
    def nnzb(self) -> jax.Array:
        return self.indptr[-1]


@_register
@dataclasses.dataclass(frozen=True)
class TopKRows:
    """Eq. (2) of the paper: exactly-k-per-row sparse activations."""

    values: jax.Array  # (n, k)
    indices: jax.Array  # (n, k) int32
    shape: Tuple[int, int] = _static()  # (n, d_full)

    @property
    def k(self) -> int:
        return self.values.shape[1]

    def to_dense(self) -> jax.Array:
        n, d = self.shape
        out = jnp.zeros((n, d), self.values.dtype)
        rows = jnp.arange(n)[:, None]
        return out.at[rows, self.indices].add(self.values)


# ---------------------------------------------------------------------------
# Constructors / converters.  Dense-side constructors are host/test helpers;
# they accept a static ``capacity`` so results stay jit-compatible.
# ---------------------------------------------------------------------------

def csr_from_dense(x, capacity: int | None = None) -> CSR:
    """Dense (n, m) -> CSR.  Host-side helper (uses numpy for compaction)."""
    x = np.asarray(x)
    n, m = x.shape
    rows, cols = np.nonzero(x)
    vals = x[rows, cols]
    nnz = len(rows)
    cap = capacity if capacity is not None else max(nnz, 1)
    if nnz > cap:
        raise ValueError(f"capacity {cap} < nnz {nnz}")
    indptr = np.zeros(n + 1, np.int32)
    np.add.at(indptr[1:], rows, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    indices = np.zeros(cap, np.int32)
    data = np.zeros(cap, x.dtype)
    indices[:nnz] = cols
    data[:nnz] = vals
    return CSR(jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(data), (n, m))


def csr_from_coo(rows, cols, vals, shape, capacity: int | None = None) -> CSR:
    """COO triplets (host numpy) -> CSR, sorting by (row, col) and merging dups."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals)
    n, m = shape
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    # merge duplicates
    if len(rows):
        key = rows * m + cols
        uniq, inv = np.unique(key, return_inverse=True)
        merged = np.zeros(len(uniq), vals.dtype)
        np.add.at(merged, inv, vals)
        rows, cols, vals = (uniq // m).astype(np.int64), (uniq % m).astype(np.int64), merged
    nnz = len(rows)
    cap = capacity if capacity is not None else max(nnz, 1)
    indptr = np.zeros(n + 1, np.int32)
    np.add.at(indptr[1:], rows, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    indices = np.zeros(cap, np.int32)
    data = np.zeros(cap, vals.dtype)
    indices[:nnz] = cols
    data[:nnz] = vals
    return CSR(jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(data), (n, m))


def csr_to_dense(a: CSR) -> jax.Array:
    out = jnp.zeros(a.shape, a.data.dtype)
    rid = a.row_ids()
    # padding slots have rid == n_rows -> scattered into a dropped row
    out = jnp.zeros((a.n_rows + 1, a.n_cols), a.data.dtype).at[rid, a.indices].add(
        jnp.where(a.valid_mask(), a.data, 0)
    )
    return out[: a.n_rows]


def ell_from_dense(x, k_cap: int | None = None) -> ELL:
    x = np.asarray(x)
    n, m = x.shape
    per_row = (x != 0).sum(axis=1)
    k = k_cap if k_cap is not None else max(int(per_row.max(initial=0)), 1)
    indices = -np.ones((n, k), np.int32)
    data = np.zeros((n, k), x.dtype)
    for i in range(n):
        cols = np.nonzero(x[i])[0][:k]
        indices[i, : len(cols)] = cols
        data[i, : len(cols)] = x[i, cols]
    return ELL(jnp.asarray(indices), jnp.asarray(data), (n, m))


def ell_to_dense(a: ELL) -> jax.Array:
    n, m = a.shape
    mask = a.valid_mask()
    safe_idx = jnp.where(mask, a.indices, m)  # scatter padding into a dropped col
    out = jnp.zeros((n, m + 1), a.data.dtype)
    rows = jnp.arange(n)[:, None]
    out = out.at[rows, safe_idx].add(jnp.where(mask, a.data, 0))
    return out[:, :m]


@functools.partial(jax.jit, static_argnames=("k_cap",))
def csr_to_ell(a: CSR, k_cap: int) -> ELL:
    """CSR -> ELL with static per-row capacity ``k_cap`` (jit-compatible).

    Each row gathers its first ``k_cap`` slots (a row's later entries are
    dropped).  A gather rather than a scatter into the (n, k_cap) planes:
    the TPU compiler takes seconds over such a scatter at published matrix
    sizes."""
    starts = a.indptr[:-1]
    offs = jnp.arange(k_cap, dtype=jnp.int32)[None, :]
    valid = offs < (a.indptr[1:] - starts)[:, None]
    pos = jnp.clip(starts[:, None] + offs, 0, max(a.capacity - 1, 0))
    if a.capacity == 0:
        return ELL(jnp.full((a.n_rows, k_cap), -1, jnp.int32),
                   jnp.zeros((a.n_rows, k_cap), a.data.dtype), a.shape)
    indices = jnp.where(valid, a.indices[pos], -1).astype(jnp.int32)
    data = jnp.where(valid, a.data[pos], 0).astype(a.data.dtype)
    return ELL(indices, data, a.shape)


def ell_to_csr(a: ELL, capacity: int | None = None) -> CSR:
    """ELL -> CSR (jit-compatible; capacity defaults to n*k_cap)."""
    n, m = a.shape
    cap = capacity if capacity is not None else a.n_rows * a.k_cap
    counts = a.row_nnz()
    indptr = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)]).astype(jnp.int32)
    mask = a.valid_mask()
    # compact valid entries left within each row, then scatter to flat offsets
    order = jnp.argsort(~mask, axis=1, stable=True)  # valid first
    rows = jnp.arange(n)[:, None]
    cidx = jnp.take_along_axis(a.indices, order, axis=1)
    cdat = jnp.take_along_axis(a.data, order, axis=1)
    within = jnp.arange(a.k_cap)[None, :]
    flat_pos = indptr[:-1][:, None] + within
    ok = within < counts[:, None]
    flat_pos = jnp.where(ok, flat_pos, cap)
    indices = jnp.zeros(cap + 1, jnp.int32).at[flat_pos].set(jnp.where(ok, cidx, 0))[:cap]
    data = jnp.zeros(cap + 1, a.data.dtype).at[flat_pos].set(jnp.where(ok, cdat, 0))[:cap]
    return CSR(indptr, indices, data, a.shape)


def bsr_from_dense(x, block_shape: Tuple[int, int], capacity: int | None = None) -> BSR:
    """Dense -> BSR keeping blocks with any nonzero (host-side helper)."""
    x = np.asarray(x)
    n, m = x.shape
    br, bc = block_shape
    assert n % br == 0 and m % bc == 0, (n, m, block_shape)
    nbr, nbc = n // br, m // bc
    blocks4 = x.reshape(nbr, br, nbc, bc).transpose(0, 2, 1, 3)
    nz = np.abs(blocks4).sum(axis=(2, 3)) != 0
    rows, cols = np.nonzero(nz)
    nnzb = len(rows)
    cap = capacity if capacity is not None else max(nnzb, 1)
    indptr = np.zeros(nbr + 1, np.int32)
    np.add.at(indptr[1:], rows, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    indices = np.zeros(cap, np.int32)
    blocks = np.zeros((cap, br, bc), x.dtype)
    indices[:nnzb] = cols
    blocks[:nnzb] = blocks4[rows, cols]
    return BSR(jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(blocks), (n, m))


def bsr_to_dense(a: BSR) -> jax.Array:
    br, bc = a.block_shape
    nbr = a.n_brows
    nbc = a.shape[1] // bc
    cap = a.indices.shape[0]
    p = jnp.arange(cap, dtype=jnp.int32)
    rid = jnp.searchsorted(a.indptr, p, side="right").astype(jnp.int32) - 1
    valid = p < a.nnzb
    rid = jnp.where(valid, rid, nbr)
    out = jnp.zeros((nbr + 1, nbc, br, bc), a.blocks.dtype)
    out = out.at[rid, a.indices].add(jnp.where(valid[:, None, None], a.blocks, 0))
    return out[:nbr].transpose(0, 2, 1, 3).reshape(a.shape)
