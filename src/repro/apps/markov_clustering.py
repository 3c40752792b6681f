"""Markov Clustering (paper Algorithm 6) on the SpGEMM pipeline.

Expansion (A^e) is the SpGEMM; pruning keeps top-k per column above θ;
inflation is a Hadamard power + column normalization.  Each iteration's
expansion runs through the full multi-phase pipeline (grouping →
allocation → accumulation), exactly the iterative-SpGEMM workload the
paper benchmarks in Fig. 7/8.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import numpy as np

from repro.core import executor
from repro.core.spgemm import PlanCache, spgemm, spgemm_streamed
from repro.sparse.formats import CSR, csr_from_coo
from repro.sparse.ops import (
    csr_column_normalize,
    csr_hadamard_power,
    csr_prune_columns,
)


@dataclasses.dataclass
class MCLResult:
    matrix: CSR
    clusters: np.ndarray  # cluster id per node
    n_iterations: int
    spgemm_info: List[dict]
    # Alg. 1 + Table-I setups skipped because the expansion's support was
    # unchanged from an earlier iteration (``reuse_plan=True``).
    plan_cache_hits: int = 0


def add_self_loops(g: CSR, weight: float = 1.0) -> CSR:
    """AddSelfLoops(G) — host-side structural edit."""
    indptr = np.asarray(g.indptr)
    indices = np.asarray(g.indices)
    data = np.asarray(g.data)
    nnz = int(indptr[-1])
    rows = np.repeat(np.arange(g.n_rows), indptr[1:] - indptr[:-1])
    rows = np.concatenate([rows, np.arange(g.n_rows)])
    cols = np.concatenate([indices[:nnz], np.arange(g.n_rows)])
    vals = np.concatenate([data[:nnz], np.full(g.n_rows, weight, data.dtype)])
    return csr_from_coo(rows, cols, vals, g.shape)


def _host_entries(a: CSR):
    """(row-major flat keys, values) of ``a``'s stored entries, on the host."""
    indptr = np.asarray(a.indptr).astype(np.int64)
    nnz = int(indptr[-1])
    rows = np.repeat(np.arange(a.n_rows, dtype=np.int64), np.diff(indptr))
    keys = rows * a.n_cols + np.asarray(a.indices)[:nnz]
    return keys, np.asarray(a.data)[:nnz]


def _change(a: CSR, b: CSR) -> float:
    """Largest entrywise difference between two CSRs (of their dense forms,
    computed from the stored entries on the host)."""
    ka, va = _host_entries(a)
    kb, vb = _host_entries(b)
    keys, inv = np.unique(np.concatenate([ka, kb]), return_inverse=True)
    diff = np.zeros(len(keys), np.float64)
    np.add.at(diff, inv, np.concatenate([va.astype(np.float64),
                                         -vb.astype(np.float64)]))
    return float(np.abs(diff).max(initial=0.0))


def drop_zeros(a: CSR) -> CSR:
    """Remove the explicitly stored zeros — host-side structural edit.

    ``csr_prune_columns`` zeroes pruned entries in place; dropping them is
    what makes Prune shrink the next expansion (on a power-law graph the
    retained structure of A² multiplied by itself runs to ~10⁹ intermediate
    products).  The capacity is pow2-quantized, like the SpGEMM output's,
    so iterations keep reusing compiled programs."""
    keys, vals = _host_entries(a)
    keep = vals != 0
    keys, vals = keys[keep], vals[keep]
    return csr_from_coo(keys // a.n_cols, keys % a.n_cols, vals, a.shape,
                        capacity=executor.next_pow2(max(len(vals), 1)))


@functools.partial(jax.jit, static_argnames=("k",))
def _prune_inflate(b: CSR, theta, k: int, r) -> CSR:
    """Prune (drop < theta, top-k per column), inflate (Hadamard power) and
    column-normalize, as one compiled program."""
    c = csr_prune_columns(b, theta, k)
    return csr_column_normalize(csr_hadamard_power(c, r))


def interpret_clusters(a: CSR) -> np.ndarray:
    """Connected components of the converged matrix's support (attractors)."""
    import networkx as nx
    indptr = np.asarray(a.indptr)
    indices = np.asarray(a.indices)
    data = np.asarray(a.data)
    g = nx.Graph()
    g.add_nodes_from(range(a.n_rows))
    for i in range(a.n_rows):
        for p in range(indptr[i], indptr[i + 1]):
            if data[p] > 1e-6:
                g.add_edge(i, int(indices[p]))
    labels = np.zeros(a.n_rows, np.int64)
    for cid, comp in enumerate(nx.connected_components(g)):
        for v in comp:
            labels[v] = cid
    return labels


def mcl(
    g: CSR,
    e: int = 2,
    r: float = 2.0,
    theta: float = 1e-4,
    k: int = 32,
    max_iters: int = 16,
    tol: float = 1e-4,
    method: str = "sort",
    gather: str = "auto",
    schedule: str = "grouped",
    mesh=None,
    reuse_plan: bool = True,
    pipeline: str = "two_wave",
    sizing: str = "auto",
    stream: int = None,
    prefetch: int = 2,
    on_budget: str = "error",
) -> MCLResult:
    """Algorithm 6.  ``e=2`` expansion = one SpGEMM self-product per iter.

    Each iteration's expansion goes through the plan-compiled executor;
    ``gather``/``schedule`` expose the paper's AIA ablation axes, and
    repeated iterations reuse the executor's program cache (no re-tracing).
    ``mesh`` shards every expansion's plan across the mesh's devices; the
    per-shard programs stay cache-warm across iterations.
    ``reuse_plan`` keeps a per-run ``PlanCache`` over the expansions: once
    the clustering's support stabilizes (the common case well before
    value convergence), every further iteration skips Algorithm 1 IP
    counting and Table-I binning entirely — the hit count is reported as
    ``MCLResult.plan_cache_hits``.
    ``pipeline`` selects the executor sync structure (``"two_wave"`` =
    one coalesced allocate sync + device-side reassembly per expansion;
    ``"legacy"`` = the per-chunk-sync reference path).
    ``sizing`` selects the executor's output sizing (``"planned"`` = the
    sync-free Alg. 1 bound path, the default for ``method="fused_hash"``;
    ``"measured"`` = the uniqueCount-sync escape hatch).
    ``method="auto"`` turns on per-bin adaptive dispatch — MCL's repeated
    same-support expansions are the ``AutotuneCache``'s convergence case;
    any method value is validated up front.
    ``stream`` routes every expansion through the out-of-core streamed
    lane (``spgemm_streamed``) with ``stream`` rows per tile and
    ``prefetch`` tiles in flight — bit-identical to the monolithic run,
    but with a per-tile device working set, so a graph whose monolithic
    expansion exceeds ``executor.set_device_budget`` still clusters end
    to end.  ``reuse_plan`` then caches *tile* plans: once the support
    stabilizes, every tile of every further expansion is a plan hit.
    ``stream=None`` (default) keeps the monolithic expansion.
    ``on_budget="stream"`` makes monolithic expansions degrade gracefully
    when an iteration's plan exceeds ``executor.set_device_budget``: that
    expansion re-routes through the streamed lane with auto-derived
    ``tile_rows`` (bit-identical) instead of raising
    ``DeviceBudgetExceeded`` — see docs/resilience.md.
    """
    method = executor.resolve_engine(method)
    stream = None if stream is None else executor.resolve_tile_rows(stream)
    on_budget = executor.resolve_on_budget(on_budget)
    a = jax.jit(csr_column_normalize)(add_self_loops(g))
    plan_cache = PlanCache() if reuse_plan else None
    infos = []
    it = 0
    for it in range(1, max_iters + 1):
        prev = a
        # Expansion: B <- A^e  (e-1 SpGEMM products)
        b = a
        for _ in range(e - 1):
            if stream is not None:
                res = spgemm_streamed(
                    b, a, tile_rows=stream, prefetch=prefetch,
                    engine=method, gather=gather, schedule=schedule,
                    mesh=mesh, plan=plan_cache, pipeline=pipeline,
                    sizing=sizing)
            else:
                res = spgemm(b, a, engine=method, gather=gather,
                             schedule=schedule, mesh=mesh, plan=plan_cache,
                             pipeline=pipeline, sizing=sizing,
                             on_budget=on_budget)
            infos.append(res.info)
            b = res.c
        # Prune (drop < theta, keep top-k per column), inflation (Hadamard
        # power + column normalize), then compaction of the pruned entries
        a = drop_zeros(_prune_inflate(b, theta, k, r))
        if a.shape == prev.shape and _change(a, prev) < tol:
            break
    clusters = interpret_clusters(a)
    return MCLResult(matrix=a, clusters=clusters, n_iterations=it,
                     spgemm_info=infos,
                     plan_cache_hits=plan_cache.hits if plan_cache else 0)
