"""Algorithm 1 — Intermediate Product Counting.

For C = A·B (Gustavson row-wise), row i of C is built from
``IP[i] = Σ_{j ∈ row_i(A)} nnz(B[col_A[j]])`` intermediate products.
IP drives the paper's load-balancing (Table I) and the hash-table sizing.

The paper notes this O(nnz(A)) pass costs >10% of GPU runtime because of
atomic adds to global memory; in JAX it is a gather + segment-sum, and on
TPU the gather ``row_nnz_B[col_A[j]]`` is itself an AIA-range-1 access.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sparse.formats import CSR


@jax.jit
def intermediate_products(a: CSR, b: CSR) -> jax.Array:
    """IP per row of A (int32, shape (a.n_rows,)). Algorithm 1, vectorized."""
    row_nnz_b = b.row_nnz()  # (= rpt_B[col+1] - rpt_B[col] precomputed)
    valid = a.valid_mask()
    contrib = jnp.where(valid, jnp.take(row_nnz_b, a.indices, mode="clip"), 0)
    rid = a.row_ids()
    ip = jnp.zeros(a.n_rows + 1, jnp.int32).at[rid].add(contrib.astype(jnp.int32))
    return ip[: a.n_rows]


def total_intermediate_products(a: CSR, b: CSR) -> jax.Array:
    """Σ IP — the paper's FLOP basis: GFLOPS = 2·ΣIP / time."""
    return jnp.sum(intermediate_products(a, b))


def ip_histogram(ip: jax.Array, boundaries=(32, 512, 8192)) -> jax.Array:
    """Row counts per Table-I group (log-binned)."""
    b = jnp.asarray(boundaries)
    group = jnp.searchsorted(b, ip, side="right")  # 0..len(boundaries)
    return jnp.zeros(len(boundaries) + 1, jnp.int32).at[group].add(1)
