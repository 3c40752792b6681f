"""Uniform random undirected graph with exactly round(n·avg_degree / 2)
distinct edges drawn from the structure seed, no self-loops, stored both
ways: (indptr, indices) of a symmetric pattern, columns sorted in each row.

Pairs are drawn uniformly until enough distinct ones are in hand; a surplus
is thinned by a uniform choice.  Degrees are then Poisson-like about
avg_degree."""

import numpy as np


def generate(spec: dict, n: int, avg_degree: float):
    rng = np.random.default_rng(spec["structure_seed"])
    m = round(n * avg_degree / 2)
    keys = np.empty(0, np.int64)
    while len(keys) < m:
        draw = (m - len(keys)) * 21 // 20 + 1024
        i, j = rng.integers(0, n, draw), rng.integers(0, n, draw)
        keep = i != j
        lo, hi = np.minimum(i, j)[keep], np.maximum(i, j)[keep]
        keys = np.unique(np.concatenate([keys, lo * n + hi]))
    if len(keys) > m:
        keys = np.sort(rng.choice(keys, m, replace=False))
    # keys run by (lo, hi): the upper triangle is in CSR order already; the
    # lower one, (hi, lo), comes in order from a stable sort on hi.
    lo, hi = (keys // n).astype(np.int32), (keys % n).astype(np.int32)
    n_lower, n_upper = np.bincount(hi, minlength=n), np.bincount(lo, minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(n_lower + n_upper, out=indptr[1:])
    indices = np.empty(2 * m, np.int32)
    # each row holds its lower entries (columns < row), then its upper ones
    lower_start = np.repeat(indptr[:-1], n_lower)
    order = np.argsort(hi, kind="stable")
    indices[lower_start + _rank_in_runs(n_lower)] = lo[order]
    upper_start = np.repeat(indptr[:-1] + n_lower, n_upper)
    indices[upper_start + _rank_in_runs(n_upper)] = hi
    return indptr.astype(np.int32), indices


def _rank_in_runs(counts):
    """0, 1, ..., c-1 for each run length c of ``counts``, concatenated."""
    total = int(counts.sum())
    starts = np.repeat(np.cumsum(counts) - counts, counts)
    return np.arange(total, dtype=np.int64) - starts
