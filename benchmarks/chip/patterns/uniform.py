"""Uniform random digraph: n·avg_degree edges drawn uniformly from the
structure seed, self-loops dropped, duplicates merged."""

import numpy as np

from workload import csr_from_edges


def generate(spec: dict, n: int, avg_degree: float):
    rng = np.random.default_rng(spec["structure_seed"])
    m = int(n * avg_degree)
    rows, cols = rng.integers(0, n, m), rng.integers(0, n, m)
    keep = rows != cols
    return csr_from_edges(rows[keep], cols[keep], n)
