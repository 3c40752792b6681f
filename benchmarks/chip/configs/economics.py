"""Plain reference for the economics self-product C = A @ A, and the
comparison that decides ``correct``.

The reference is SciPy's CSR product in float64 on the host, from the
pattern and values the benchmark generated; it imports nothing of the
program.  The control computes the same product in bfloat16 (values stored
in bfloat16, the product rounded to bfloat16), the step below the float32
that the configuration states.

Two numbers are compared, each with its limit (PERF.md gives the readings
the limits were set from):

* ``pattern_mismatch``: rows of C whose column ids differ from the
  reference's, or whose length does; an exact comparison, limit 0;
* ``value_gap``: the largest relative gap |c - r| / |r| over C's entries.
  Every value is positive, so no entry cancels and the gap of a float32
  product stays near its rounding.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

LIMITS = {"pattern_mismatch": 0, "value_gap": 1e-5}


def _matrix(indptr, indices, values, n: int, dtype):
    return sp.csr_matrix(
        (np.asarray(values).astype(dtype), np.asarray(indices), np.asarray(indptr)),
        shape=(n, n),
    )


def _product(m):
    c = (m @ m).tocsr()
    c.sort_indices()
    return c


def reference(indptr, indices, values, n: int):
    """C = A @ A in float64 (SciPy), column ids sorted within each row."""
    return _product(_matrix(indptr, indices, values, n, np.float64))


def control(indptr, indices, values, n: int):
    """The reference in bfloat16: values rounded to bfloat16, the product
    rounded to bfloat16."""
    from ml_dtypes import bfloat16

    v = np.asarray(values, np.float32).astype(bfloat16).astype(np.float32)
    c = _product(_matrix(indptr, indices, v, n, np.float32))
    c.data = c.data.astype(bfloat16).astype(np.float64)
    return c


def pattern_mismatch(indptr, indices, ref) -> int:
    """Rows whose length or column ids differ from the reference's."""
    indptr = np.asarray(indptr, np.int64)
    lengths_differ = np.diff(indptr) != np.diff(ref.indptr)
    if lengths_differ.any():
        return int(lengths_differ.sum())
    wrong = np.asarray(indices)[: indptr[-1]] != ref.indices
    rows = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    return int(np.unique(rows[wrong]).size)


def compare(indptr, indices, data, ref) -> dict:
    """The numbers compared for one product, by name."""
    mismatch = pattern_mismatch(indptr, indices, ref)
    if mismatch:
        return {"pattern_mismatch": mismatch, "value_gap": float("inf")}
    nnz = int(np.asarray(indptr)[-1])
    got = np.asarray(data)[:nnz].astype(np.float64)
    gap = np.abs(got - ref.data) / np.abs(ref.data) if nnz else np.zeros(0)
    return {"pattern_mismatch": 0, "value_gap": float(gap.max(initial=0.0))}
