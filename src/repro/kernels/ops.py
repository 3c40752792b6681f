"""Jit'd public wrappers: Pallas on TPU, interpret-mode Pallas or XLA on CPU.

``backend`` resolution (``kernels.backend.resolve_backend``):
  * "pallas"    — compiled Pallas (TPU target).
  * "interpret" — Pallas kernel body executed in Python (CPU validation).
  * "xla"       — pure-jnp fallback (also the software-only / "without AIA"
                  baseline used throughout EXPERIMENTS.md).
"""
from __future__ import annotations

from repro.kernels import aia_gather as _aia
from repro.kernels import ref as _ref
from repro.kernels import spgemm_bsr as _bsr
from repro.kernels import topk_spmm as _topk
from repro.kernels.backend import Backend, resolve_backend

__all__ = ["Backend", "resolve_backend", "aia_ranged_gather", "gather_rows",
           "bsr_spmm", "hash_accumulate", "topk_spmm", "block_topk_spmm"]


def aia_ranged_gather(x, idx, r: int = 1, backend: Backend = "auto"):
    be = resolve_backend(backend)
    if be == "xla":
        return _ref.aia_ranged_gather(x, idx, r)
    return _aia.aia_ranged_gather(x, idx, r, interpret=(be == "interpret"))


def gather_rows(x, idx, rows_per_block: int | None = None,
                backend: Backend = "auto"):
    be = resolve_backend(backend)
    if be == "xla":
        return _ref.gather_rows(x, idx)
    return _aia.gather_rows(x, idx, rows_per_block, interpret=(be == "interpret"))


def bsr_spmm(rowptr, colidx, a_blocks, b, max_blocks_per_row: int,
             backend: Backend = "auto"):
    be = resolve_backend(backend)
    if be == "xla":
        from repro.core.spgemm_bsr import bsr_spgemm_dense_rhs
        from repro.sparse.formats import BSR
        bs = a_blocks.shape[1]
        n_brows = rowptr.shape[0] - 1
        a = BSR(rowptr, colidx, a_blocks,
                (n_brows * bs, b.shape[0]))
        return bsr_spgemm_dense_rhs(a, b)
    return _bsr.bsr_spmm(rowptr, colidx, a_blocks, b, max_blocks_per_row,
                         interpret=(be == "interpret"))


def hash_accumulate(keys, vals, table_cap: int, backend: Backend = "auto"):
    """Algorithm-4 accumulation; XLA fallback = the vmapped hash engine.

    Contract note: the kernel emits the table in *probe order* (unsorted);
    the XLA fallback emits a column-sorted prefix.  Both carry the same
    (col → Σ val) content and uniqueCount; callers needing CSR order sort
    afterward (Algorithm 5 step 3)."""
    be = resolve_backend(backend)
    if be == "xla":
        from repro.core import phases
        return phases.accumulate_hash(keys, vals, table_cap)
    from repro.kernels import hash_accum as _ha
    return _ha.hash_accumulate(keys, vals, table_cap,
                               interpret=(be == "interpret"))


def topk_spmm(vals, idx, w2, backend: Backend = "auto"):
    be = resolve_backend(backend)
    if be == "xla":
        return _ref.topk_spmm(vals, idx, w2)
    return _topk.topk_spmm(vals, idx, w2, interpret=(be == "interpret"))


def block_topk_spmm(h_kept, bidx, w2, block: int = 128, backend: Backend = "auto"):
    be = resolve_backend(backend)
    if be == "xla":
        return _ref.block_topk_spmm(h_kept, bidx, w2, block)
    return _topk.block_topk_spmm(h_kept, bidx, w2, block,
                                 interpret=(be == "interpret"))
