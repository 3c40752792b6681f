"""Self-product drive: C = A @ A through ``repro.core.spgemm.spgemm``, the
pattern fixed by the configuration, a fresh value array for every call, one
call at a time through one ``PlanCache``.

The mix gives ``warm_calls`` (set-up's calls), ``nominal_call_s`` and
``min_calls`` (the window makes max(min_calls, round(seconds /
nominal_call_s)) calls) and ``checked_calls`` (how many of them the check
compares, drawn from the seed, the last always among them).
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from workload import annotate, pattern, seed32


class Drive:
    def __init__(self, config, traffic, seed: int, seconds: float, reference):
        self.cfg, self.traffic, self.seed, self.ref = config, traffic, seed, reference
        self.n_calls = max(traffic["min_calls"], round(seconds / traffic["nominal_call_s"]))
        rng = np.random.default_rng(seed32(seed, 1))
        k = min(traffic["checked_calls"] - 1, self.n_calls - 1)
        self.checked = sorted(
            set(rng.choice(self.n_calls - 1, k, replace=False).tolist()) | {self.n_calls - 1}
        )

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core import executor
        from repro.sparse.formats import CSR

        cfg = self.cfg
        n = cfg["rows"]
        self.indptr, self.indices = pattern(cfg["pattern"], n, cfg["nnz_per_row"])
        nnz = int(self.indptr[-1])
        lo, hi = cfg["values"]["low"], cfg["values"]["high"]
        dtype = jnp.dtype(cfg["dtype"])

        @jax.jit
        def values(key, i):
            return jax.random.uniform(jax.random.fold_in(key, i), (nnz,), dtype, lo, hi)

        key = jax.random.PRNGKey(seed32(self.seed))
        warm = self.traffic["warm_calls"]
        self.pool = [values(key, i) for i in range(warm + self.n_calls)]
        ip_d, ix_d = jnp.asarray(self.indptr), jnp.asarray(self.indices)
        self.operands = [CSR(ip_d, ix_d, v, (n, n)) for v in self.pool]
        self.plan = executor.PlanCache()
        for a in self.operands[:warm]:
            self._call(a)

    def _call(self, a):
        import jax

        spgemm = importlib.import_module("repro.core.spgemm").spgemm
        with annotate("product"):
            res = spgemm(a, a, plan=self.plan)
        with annotate("block"):
            jax.block_until_ready((res.c.indptr, res.c.indices, res.c.data))
        return res.c

    def window(self):
        """The measured calls: the window's seconds, the calls made, the
        seconds of each, and the mean, ``product_s``."""
        warm = self.traffic["warm_calls"]
        self.kept = {}
        ends = []
        t0 = time.perf_counter()
        for i in range(self.n_calls):
            with annotate("values"):
                a = self.operands[warm + i]
            c = self._call(a)
            if i in self.checked:
                self.kept[i] = c
            ends.append(time.perf_counter())
        seconds = ends[-1] - t0
        item_s = np.diff([t0] + ends).tolist()
        return {
            "seconds": seconds,
            "items": self.n_calls,
            "item_s": item_s,
            "product_s": seconds / self.n_calls,
        }

    def check(self):
        """Compare the kept products with the reference; returns
        ({name: worst reading}, failed calls)."""
        from counts import intermediate_products, product_counts

        n, warm = self.cfg["rows"], self.traffic["warm_calls"]
        worst, failed = {}, 0
        nnz_c = None
        for i, c in sorted(self.kept.items()):
            ip = np.asarray(c.indptr)
            nnz = int(ip[-1])
            ix, dt = np.asarray(c.indices)[:nnz], np.asarray(c.data)[:nnz]
            ref = self.ref.reference(self.indptr, self.indices, np.asarray(self.pool[warm + i]), n)
            nnz_c = ref.nnz
            got = self.ref.compare(ip, ix, dt, ref)
            failed += any(got[k] > self.ref.LIMITS[k] for k in got)
            for k, v in got.items():
                worst[k] = max(worst.get(k, v), v)
        self.kept.clear()
        nnz_a = int(self.indptr[-1])
        ip_count = intermediate_products(self.indptr, self.indices, self.indptr)
        itemsize = np.dtype(self.cfg["dtype"]).itemsize
        self.counts = product_counts(n, nnz_a, nnz_a, nnz_c, ip_count, itemsize)
        return worst, failed
