"""Full-batch GraphSAGE training drive: the ``train`` drive's window (one
``repro.apps.gnn.train_gnn`` call of max(min_steps, round(seconds /
nominal_step_s)) steps) over the configuration's undirected graph, with
mean aggregation over D^-1 A and multi-label targets.

Set-up builds D^-1 A on the host from the structure seed, draws the
features (uniform in [0, 1)) and the labels (Bernoulli(0.5) per node and
task, float32, so the program takes its binary cross-entropy) from
``--seed``, and runs ``warm_steps`` steps.  The check frees the program's
state, then trains the configuration's reference from the same seed.
"""

from __future__ import annotations

import importlib

import numpy as np

from sage_counts import sage_step_counts
from workload import load, pattern, seed32

Train = load("drives", "train").Drive


class Drive(Train):
    def gnn_config(self):
        gnn = importlib.import_module("repro.apps.gnn")
        c = self.cfg
        return gnn.GNNConfig(
            arch=c["arch"],
            n_layers=c["layers"],
            d_in=c["features"],
            d_hidden=c["hidden"],
            n_classes=c["tasks"],
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
        indptr, cols = pattern(cfg["graph"], n, 2 * cfg["edges"] / n)
        degree = np.diff(indptr)
        rows = np.repeat(np.arange(n, dtype=np.int32), degree)
        vals = (1.0 / np.maximum(degree, 1)).astype(np.float32)[rows]
        self.graph = (rows, cols, vals)
        self.a_hat = CSR(jnp.asarray(indptr), jnp.asarray(cols), jnp.asarray(vals), (n, n))

        @jax.jit
        def data(key):
            kx, ky = jax.random.split(key)
            x = jax.random.uniform(kx, (n, cfg["features"]), jnp.dtype(cfg["dtype"]))
            labels = jax.random.bernoulli(ky, 0.5, (n, cfg["tasks"])).astype(jnp.float32)
            return x, labels

        self.x, self.labels = data(jax.random.PRNGKey(seed32(self.seed, 2)))
        self.init_seed = seed32(self.seed, 3)
        self._train(self.traffic["warm_steps"], seed32(self.seed, 4))

    def check(self):
        c = self.cfg
        params = {k: np.asarray(v) for k, v in self.params.items()}
        self.params = self.a_hat = None  # the program's state is freed before the reference runs
        ref = self.ref.reference(c, self.graph, self.x, self.labels, self.init_seed, self.n_steps)
        got = self.ref.compare(self.losses, params, ref)
        failed = int(any(got[k] > self.ref.LIMITS[k] for k in got))
        sizes = (c["features"], c["hidden"], c["tasks"], c["layers"], c["topk"])
        self.counts = sage_step_counts(c["nodes"], len(self.graph[0]), *sizes)
        return got, failed
