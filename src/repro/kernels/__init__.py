"""Pallas TPU kernels — the AIA hardware technique, TPU-native.

The paper's AIA engine lives in the HBM base die and serves *ranged indirect
accesses* as bulk sequential streams.  The TPU-native equivalent keeps the
index arrays in SMEM, where they program DMAs — through the
`BlockSpec.index_map` of a scalar-prefetch grid, or one descriptor per
row — so the DMA engine, not the compute core, resolves the indirection.
See DESIGN.md §2 for the full mapping table.

Kernels (each with `ops.py` jit'd wrapper + `ref.py` pure-jnp oracle):

* ``aia_gather``  — the AIA primitive itself: out[i] = x[idx[i]·R : +R].
* ``spgemm_bsr``  — block-row Gustavson accumulation on the MXU.
* ``topk_spmm``   — Eq. (1) sparse-activation FFN matmul (per-token and
                    MXU-aligned block-structured variants).
* ``hash_accum``  — Algorithm 4 (linear-probing insert/accumulate) with the
                    table in SMEM scratch (scalar stores), one output row
                    per grid step, every Table-I capacity up to 65536.
* ``flash_attention`` — fused online-softmax attention (scores stay in
                    VMEM; the §Perf memory-roofline fix).

All kernels are written for TPU and validated on CPU with
``interpret=True``; ``backend.resolve_interpret`` is the one policy for
when a kernel runs compiled.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
