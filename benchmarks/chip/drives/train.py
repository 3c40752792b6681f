"""Full-batch GNN training drive: one ``repro.apps.gnn.train_gnn`` call of
a fixed number of steps fills the window.

The mix gives ``warm_steps`` (set-up's call, which compiles the step),
``nominal_step_s`` and ``min_steps`` (the window's call makes
max(min_steps, round(seconds / nominal_step_s)) steps).  The check compares
the window's losses and final weights with the configuration's reference.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from workload import annotate, gcn_adjacency, pattern, seed32


class Drive:
    def __init__(self, config, traffic, seed: int, seconds: float, reference):
        self.cfg, self.traffic, self.seed, self.ref = config, traffic, seed, reference
        self.n_steps = max(traffic["min_steps"], round(seconds / traffic["nominal_step_s"]))

    def gnn_config(self):
        gnn = importlib.import_module("repro.apps.gnn")
        c = self.cfg
        return gnn.GNNConfig(
            arch=c["arch"],
            n_layers=c["layers"],
            d_in=c["features"],
            d_hidden=c["hidden"],
            n_classes=c["classes"],
            topk=c["topk"],
            sparse_mode=c["sparse_mode"],
            gather=c["gather"],
        )

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.sparse.formats import CSR

        cfg = self.cfg
        n = cfg["nodes"]
        indptr, indices = pattern(cfg["graph"], n, cfg["graph"]["avg_degree"])
        indptr, rows, cols, vals = gcn_adjacency(indptr, indices, n)
        self.graph = (rows, cols, vals)
        self.a_hat = CSR(jnp.asarray(indptr), jnp.asarray(cols), jnp.asarray(vals), (n, n))

        @jax.jit
        def data(key):
            kx, ky = jax.random.split(key)
            x = jax.random.normal(kx, (n, cfg["features"]), jnp.dtype(cfg["dtype"]))
            return x, jax.random.randint(ky, (n,), 0, cfg["classes"])

        self.x, self.labels = data(jax.random.PRNGKey(seed32(self.seed, 2)))
        self.init_seed = seed32(self.seed, 3)
        self._train(self.traffic["warm_steps"], seed32(self.seed, 4))

    def _train(self, steps: int, seed: int):
        import jax

        train_gnn = importlib.import_module("repro.apps.gnn").train_gnn
        with annotate("train"), jax.default_matmul_precision(self.cfg["matmul_precision"]):
            params, losses = train_gnn(
                self.gnn_config(),
                self.a_hat,
                self.x,
                self.labels,
                n_steps=steps,
                lr=self.cfg["optimizer"]["lr"],
                seed=seed,
            )
        with annotate("block"):
            jax.block_until_ready(params)
        return params, losses

    def window(self):
        t0 = time.perf_counter()
        self.params, self.losses = self._train(self.n_steps, self.init_seed)
        seconds = time.perf_counter() - t0
        return {"seconds": seconds, "items": self.n_steps, "train_step_s": seconds / self.n_steps}

    def check(self):
        from counts import gcn_step_counts

        c = self.cfg
        params = {k: np.asarray(v) for k, v in self.params.items()}
        self.params = None  # the program's state is freed before the reference runs
        ref = self.ref.reference(c, self.graph, self.x, self.labels, self.init_seed, self.n_steps)
        got = self.ref.compare(self.losses, params, ref)
        failed = int(any(got[k] > self.ref.LIMITS[k] for k in got))
        sizes = (c["features"], c["hidden"], c["classes"], c["layers"], c["topk"])
        self.counts = gcn_step_counts(c["nodes"], len(self.graph[0]), *sizes)
        return got, failed
