"""One-shot self-product drive: the ``self_product`` drive's window (C = A @ A
through ``repro.core.spgemm.spgemm``, one call at a time through one
``PlanCache``), but every call multiplies a pattern of its own.

Set-up draws from ``--seed`` one symmetric relabelling P·A·Pᵀ of the
configuration's pattern per call (the warm call's too) and a fresh value
array for each, all on the host: the operands are handed over as host CSR,
so the fingerprint, planning, binning and the transfer to the device run in
every call, and the ``PlanCache`` misses every time.  A relabelling keeps
the row lengths (so ``kb_cap``) and the rows' intermediate-product counts
(so the Table-I group sizes), and with them the shapes of every program.
The check compares the chosen calls with the reference product of that
call's matrix.
"""

from __future__ import annotations

import numpy as np

from workload import load, pattern, seed32

SelfProduct = load("drives", "self_product").Drive


def relabel(indptr, indices, perm):
    """(indptr, indices) of P·A·Pᵀ: row i is row ``perm[i]`` of A, and
    column j of A becomes column ``inverse(perm)[j]``, sorted in each row."""
    indptr = np.asarray(indptr, np.int64)
    n = len(indptr) - 1
    inverse = np.empty(n, np.int64)
    inverse[perm] = np.arange(n)
    lengths = np.diff(indptr)[perm]
    new_indptr = np.zeros(n + 1, np.int64)
    np.cumsum(lengths, out=new_indptr[1:])
    rows = np.repeat(np.arange(n, dtype=np.int64), lengths)
    source = np.repeat(indptr[:-1][perm] - new_indptr[:-1], lengths) + np.arange(len(rows))
    cols = inverse[np.asarray(indices)[source]]
    order = np.argsort(rows * n + cols, kind="stable")
    return new_indptr.astype(np.int32), cols[order].astype(np.int32)


class Drive(SelfProduct):
    def setup(self):
        from repro.core import executor
        from repro.sparse.formats import CSR

        cfg = self.cfg
        n = cfg["rows"]
        indptr, indices = pattern(cfg["pattern"], n, cfg["nnz_per_row"])
        lo, hi = cfg["values"]["low"], cfg["values"]["high"]
        dtype = np.dtype(cfg["dtype"])
        rng = np.random.default_rng(seed32(self.seed, 5))
        self.patterns, self.pool, self.operands = [], [], []
        for _ in range(self.traffic["warm_calls"] + self.n_calls):
            self.patterns.append(relabel(indptr, indices, rng.permutation(n)))
            self.pool.append(rng.uniform(lo, hi, len(indices)).astype(dtype))
            self.operands.append(CSR(*self.patterns[-1], self.pool[-1], (n, n)))
        self.plan = executor.PlanCache()
        for a in self.operands[: self.traffic["warm_calls"]]:
            self._call(a)

    def check(self):
        """The ``self_product`` check of each kept call against the
        reference product of that call's own matrix; returns ({name: worst
        reading}, failed calls)."""
        kept, warm = self.kept, self.traffic["warm_calls"]
        worst, failed = {}, 0
        for i, c in sorted(kept.items()):
            self.indptr, self.indices = self.patterns[warm + i]
            self.kept = {i: c}
            got, bad = super().check()
            failed += bad
            for k, v in got.items():
                worst[k] = max(worst.get(k, v), v)
        return worst, failed
