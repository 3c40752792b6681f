"""R-MAT digraph with the quadrant probabilities ``a``, ``b``, ``c`` of
``spec`` (Graph500's 0.57, 0.19, 0.19): n·avg_degree edges drawn from the
structure seed, those outside n x n and self-loops dropped, duplicates
merged."""

import numpy as np

from workload import csr_from_edges


def generate(spec: dict, n: int, avg_degree: float):
    a, b, c = spec["a"], spec["b"], spec["c"]
    rng = np.random.default_rng(spec["structure_seed"])
    scale = int(np.ceil(np.log2(max(n, 2))))
    m = int(n * avg_degree)
    rows = np.zeros(m, np.int64)
    cols = np.zeros(m, np.int64)
    for level in range(scale):
        r = rng.random(m)
        half = 1 << (scale - level - 1)
        rows += np.where(r >= a + b, half, 0)
        cols += np.where(((r >= a) & (r < a + b)) | (r >= a + b + c), half, 0)
    keep = (rows < n) & (cols < n) & (rows != cols)
    return csr_from_edges(rows[keep], cols[keep], n)
