"""Record the small TPU trace that ``test_spans.py`` reduces through the
span-reading per-layer metrics.

    python3 benchmarks/chip/tests/record_spans_trace.py   # on a TPU

An economics-shaped window: inside ``bench.window``, ``PRODUCTS``
self-products of one 4,096-row uniform pattern at Economics' 6.2 entries
per row, each with fresh values, through one ``PlanCache`` and default
knobs (``bench.product``, then ``bench.block``), as the ``self_product``
drive makes them; then one ``train_gnn`` call of ``STEPS`` steps of a
two-layer TopK GCN on a 1,024-node R-MAT graph (``bench.train``).  Set-up
runs one product and one 1-step call first, so nothing but the step's
re-trace compiles in the window.  Writes ``data/v5e_spans.xplane.pb`` and
prints what ``spans.py`` and the five readers make of it.
"""

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path[:0] = [str(CHIP), str(HERE.parents[2] / "src")]

ROWS, NNZ_PER_ROW = 4096, 6.2
NODES, AVG_DEGREE = 1024, 15.8
PRODUCTS, STEPS = 2, 2
OUT = HERE / "data" / "v5e_spans.xplane.pb"
READERS = {
    "accumulate_s.product": PRODUCTS,
    "operand_builds.product": PRODUCTS,
    "plan_host_s.product": PRODUCTS,
    "step_traces.train": STEPS,
    "step_dispatch_s.train": STEPS,
}


def main() -> int:
    import jax
    import jax.numpy as jnp

    import spans
    import workload
    import xplane
    from repro.apps.gnn import GNNConfig, train_gnn
    from repro.core.executor import PlanCache
    from repro.core.spgemm import spgemm
    from repro.sparse.formats import CSR

    if jax.devices()[0].platform != "tpu":
        print("record_spans_trace: needs a TPU", file=sys.stderr)
        return 1
    indptr, indices = workload.pattern(
        {"generator": "uniform", "structure_seed": 0}, ROWS, NNZ_PER_ROW
    )
    ip_d, ix_d = jnp.asarray(indptr), jnp.asarray(indices)
    key = jax.random.PRNGKey(0)
    values = [
        jax.random.uniform(jax.random.fold_in(key, i), (len(indices),), jnp.float32, 0.1, 1.1)
        for i in range(PRODUCTS + 1)
    ]
    operands = [CSR(ip_d, ix_d, v, (ROWS, ROWS)) for v in values]
    plan = PlanCache()

    def product(a):
        with workload.annotate("product"):
            c = spgemm(a, a, plan=plan).c
        with workload.annotate("block"):
            jax.block_until_ready((c.indptr, c.indices, c.data))

    g_indptr, g_indices = workload.pattern(
        {"generator": "rmat", "a": 0.57, "b": 0.19, "c": 0.19, "structure_seed": 0},
        NODES,
        AVG_DEGREE,
    )
    g_indptr, _, g_cols, g_vals = workload.gcn_adjacency(g_indptr, g_indices, NODES)
    a_hat = CSR(jnp.asarray(g_indptr), jnp.asarray(g_cols), jnp.asarray(g_vals), (NODES, NODES))
    cfg = GNNConfig(arch="gcn", n_layers=2, d_in=128, d_hidden=128, n_classes=16, topk=16)
    x = jax.random.normal(key, (NODES, cfg.d_in), jnp.float32)
    labels = jnp.arange(NODES) % cfg.n_classes

    def train(steps):
        with workload.annotate("train"):
            params, _ = train_gnn(cfg, a_hat, x, labels, n_steps=steps)
        with workload.annotate("block"):
            jax.block_until_ready(params)

    product(operands[0])
    train(1)
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            for a in operands[1:]:
                product(a)
            train(STEPS)
        jax.profiler.stop_trace()
        OUT.parent.mkdir(exist_ok=True)
        shutil.copy(xplane.find_xplane(tmp), OUT)
        spans.TRACE_DIR = Path(tmp)
        spans.main([tmp])
        summary = xplane.reduce_file(OUT)
        for name, items in READERS.items():
            value = workload.load("metrics", name).read({"items": items, "trace": summary})
            print(f"{name}={value!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
