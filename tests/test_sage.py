"""GraphSAGE training against the benchmark's plain reference, the
memory-bounded ``csr_spmm`` (chunked aggregation), the loss chosen by the
labels, and the one-shot cell's relabelled patterns."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.apps import gnn
from repro.apps.gnn import GNNConfig, init_gnn, mean_adjacency, train_gnn
from repro.core import executor
from repro.core.grouping import group_rows
from repro.core.spgemm import spgemm
from repro.sparse import ops
from repro.sparse.formats import CSR

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))
import workload  # noqa: E402

CONFIG = json.loads((CHIP / "configs" / "ogbn-proteins-sage.json").read_text())
GCN_NODES, GCN_ENTRIES = 169_343, 2_325_906  # the ogbn-arxiv GCN cell: A_hat
PROTEINS_ENTRIES = 2 * CONFIG["edges"]


def _reference():
    return workload.load_path(CHIP / "configs" / "ogbn-proteins-sage.py", "reference_sage")


def _small_graph(n=600, degree=40.0, seed=0):
    """(D^-1 A as the program's CSR, its (rows, cols, vals) on the host)."""
    spec = {"generator": "uniform_undirected", "structure_seed": seed}
    indptr, cols = workload.pattern(spec, n, degree)
    ones = np.ones(len(cols), np.float32)
    a = mean_adjacency(CSR(jnp.asarray(indptr), jnp.asarray(cols), jnp.asarray(ones), (n, n)))
    deg = np.diff(indptr)
    rows = np.repeat(np.arange(n, dtype=np.int32), deg)
    vals = (1.0 / deg).astype(np.float32)[rows]
    return a, (rows, cols, vals)


def _small_cfg():
    cfg = dict(CONFIG, nodes=600, features=8, hidden=32, tasks=12, topk=4)
    model = GNNConfig(
        arch="sage", n_layers=cfg["layers"], d_in=8, d_hidden=32, n_classes=12, topk=4, gather="xla"
    )
    return cfg, model


def test_sage_training_matches_reference():
    """Three steps of the program's GraphSAGE (mean aggregation, TopK past
    layer 0, BCE over 12 tasks) against the plain reference on the same
    seeded weights: losses, the first gradients and the final weights."""
    cfg, model = _small_cfg()
    a, graph = _small_graph()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.random((600, 8), np.float32))
    labels = jnp.asarray((rng.random((600, 12)) < 0.5).astype(np.float32))
    seed, steps = 5, 3
    ref = _reference()
    p0, p_ref, ref_losses, ref_gnorms = ref.reference(cfg, graph, x, labels, seed, steps)

    params0 = init_gnn(model, jax.random.PRNGKey(seed))
    assert set(params0) == set(p0)
    for name in p0:
        np.testing.assert_array_equal(np.asarray(params0[name]), p0[name])
    mask = jnp.ones(600, jnp.float32)
    grads = jax.grad(lambda p: gnn._loss_fn(model, p, a, x, labels, mask))(params0)
    for name, g in grads.items():
        np.testing.assert_allclose(float(jnp.linalg.norm(g)), ref_gnorms[name], rtol=1e-5)

    lr = cfg["optimizer"]["lr"]
    params, losses = train_gnn(model, a, x, labels, n_steps=steps, lr=lr, seed=seed)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-6)
    for name in p_ref:
        np.testing.assert_allclose(np.asarray(params[name]), p_ref[name], rtol=1e-4, atol=1e-5)
    got = ref.compare(losses, params, (p0, p_ref, ref_losses, ref_gnorms))
    assert all(got[k] <= ref.LIMITS[k] for k in ref.LIMITS), got


def test_sage_reference_blocks_agree():
    """The reference's blocked aggregation gives the same training whether
    its blocks cover the graph once or in many pieces."""
    cfg, _ = _small_cfg()
    _, graph = _small_graph()
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.random((600, 8), np.float32))
    labels = jnp.asarray((rng.random((600, 12)) < 0.5).astype(np.float32))
    ref = _reference()
    whole = ref.reference(cfg, graph, x, labels, 3, 2)
    ref.BLOCK = 4096
    assert len(ref.blocks_of(graph, 600, ref.BLOCK)[0]) == 6
    blocked = ref.reference(cfg, graph, x, labels, 3, 2)
    np.testing.assert_allclose(blocked[2], whole[2], rtol=1e-6)
    for name in whole[1]:
        np.testing.assert_allclose(blocked[1][name], whole[1][name], rtol=1e-4, atol=1e-6)


def test_sage_parameter_count_at_config_widths():
    """OGB's GraphSAGE on ogbn-proteins (8 -> 256 -> 256 -> 112): 193,136
    parameters, the leaderboard's count."""
    model = GNNConfig(
        arch="sage",
        n_layers=CONFIG["layers"],
        d_in=CONFIG["features"],
        d_hidden=CONFIG["hidden"],
        n_classes=CONFIG["tasks"],
    )
    shapes = jax.eval_shape(lambda: init_gnn(model, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert count == CONFIG["parameters"] == 193_136


def test_mean_adjacency_rows_average_and_empty_rows_stay_zero():
    indptr = np.array([0, 2, 2, 5], np.int32)  # row 1 is empty
    indices = jnp.asarray([1, 2, 0, 1, 2], jnp.int32)
    a = CSR(jnp.asarray(indptr), indices, jnp.ones(5, jnp.float32), (3, 3))
    m = mean_adjacency(a)
    np.testing.assert_allclose(np.asarray(m.data), [0.5, 0.5, 1 / 3, 1 / 3, 1 / 3], rtol=1e-7)
    x = jnp.arange(6, dtype=jnp.float32).reshape(3, 2)
    y = ops.csr_spmm(m, x)
    np.testing.assert_allclose(np.asarray(y), [[3, 4], [0, 0], [2, 3]], rtol=1e-6)


# ---------------------------------------------------------------------------
# csr_spmm in chunks
# ---------------------------------------------------------------------------

def _chunk_case():
    """A 40-row CSR with an empty row, a row of 23 entries (split across
    chunks of 16) and capacity padded past its stored entries."""
    rng = np.random.default_rng(3)
    lengths = rng.integers(1, 7, 40)
    lengths[5] = 0
    lengths[9] = 23
    indptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int32)
    nnz = int(indptr[-1])
    cap = nnz + 37
    indices = np.zeros(cap, np.int32)
    indices[:nnz] = rng.integers(0, 30, nnz)
    data = np.zeros(cap, np.float32)
    data[:nnz] = rng.uniform(0.1, 1.1, nnz)
    a = CSR(jnp.asarray(indptr), jnp.asarray(indices), jnp.asarray(data), (40, 30))
    x = jnp.asarray(rng.standard_normal((30, 8)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((40, 8)).astype(np.float32))
    return a, x, w


@pytest.mark.parametrize("gather", ["xla", "aia"])
def test_chunked_csr_spmm_matches_one_chunk(gather, monkeypatch):
    """Output and x-gradient of the chunked aggregation equal the one-chunk
    result (``aia`` runs the Pallas gather in interpret mode here)."""
    a, x, w = _chunk_case()

    def run():
        f = jax.jit(lambda x: ops.csr_spmm(a, x, gather=gather))
        y = f(x)
        g = jax.grad(lambda x: jnp.sum(f(x) * w))(x)
        return np.asarray(y), np.asarray(g)

    assert ops.spmm_chunks(a.capacity, 8) == (1, a.capacity)
    y1, g1 = run()
    monkeypatch.setattr(ops, "SPMM_CHUNK_BYTES", 16 * 128 * 4)  # 16 entries a chunk
    n, chunk = ops.spmm_chunks(a.capacity, 8)
    assert (n, chunk) == (-(-a.capacity // 16), 16)
    assert n * chunk > a.capacity  # the last chunk is padded
    y, g = run()
    np.testing.assert_allclose(y, y1, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(g, g1, rtol=1e-6, atol=1e-6)
    dense = np.zeros((40, 30), np.float32)
    nnz = int(a.indptr[-1])
    rows = np.repeat(np.arange(40), np.diff(np.asarray(a.indptr)))
    np.add.at(dense, (rows, np.asarray(a.indices)[:nnz]), np.asarray(a.data)[:nnz])
    np.testing.assert_allclose(y, dense @ np.asarray(x), rtol=1e-5, atol=1e-5)
    assert not y[5].any()  # the empty row


def test_spmm_chunks_at_the_cells_shapes():
    """The GCN cell's aggregations stay one chunk; proteins' split into at
    least ten, each within the budget, covering every entry."""
    for d in (128, 256):
        assert ops.spmm_chunks(GCN_ENTRIES, d) == (1, GCN_ENTRIES)
    for d in (8, 256):
        n, chunk = ops.spmm_chunks(PROTEINS_ENTRIES, d)
        assert n >= 10
        assert chunk * 4 * max(128, d) <= ops.SPMM_CHUNK_BYTES
        assert chunk % 1024 == 0
        assert (n - 1) * chunk < PROTEINS_ENTRIES <= n * chunk


def test_training_step_carries_the_csr_spmm_scope():
    """Every aggregation op of a lowered training step, forward and
    transposed, carries the ``csr_spmm`` name scope."""
    _, model = _small_cfg()
    a, _ = _small_graph(n=64, degree=6.0)
    x = jnp.ones((64, 8), jnp.float32)
    labels = jnp.zeros((64, 12), jnp.float32)
    params = init_gnn(model, jax.random.PRNGKey(0))
    mask = jnp.ones(64, jnp.float32)
    step = jax.jit(jax.grad(lambda p: gnn._loss_fn(model, p, a, x, labels, mask)))
    text = step.lower(params).as_text(debug_info=True)
    assert "jvp(csr_spmm)" in text
    assert "transpose(jvp(csr_spmm))" in text


def test_loss_is_bce_for_float_task_labels_and_cross_entropy_for_classes():
    a, _ = _small_graph(n=64, degree=6.0)
    x = jnp.asarray(np.random.default_rng(4).random((64, 8), np.float32))
    mask = jnp.ones(64, jnp.float32)
    model = GNNConfig(arch="sage", n_layers=2, d_in=8, d_hidden=16, n_classes=5, topk=4)
    params = init_gnn(model, jax.random.PRNGKey(1))
    z = np.asarray(gnn.gnn_forward(model, params, a, x), np.float64)

    y = (np.random.default_rng(5).random((64, 5)) < 0.5).astype(np.float32)
    bce = np.mean(np.maximum(z, 0) - z * y + np.log1p(np.exp(-np.abs(z))))
    got = gnn._loss_fn(model, params, a, x, jnp.asarray(y), mask)
    np.testing.assert_allclose(float(got), bce, rtol=1e-5)

    classes = np.arange(64) % 5
    logp = z - np.log(np.exp(z).sum(1, keepdims=True))
    ce = -np.mean(logp[np.arange(64), classes])
    got = gnn._loss_fn(model, params, a, x, jnp.asarray(classes), mask)
    np.testing.assert_allclose(float(got), ce, rtol=1e-5)


# ---------------------------------------------------------------------------
# economics.oneshot: relabelled patterns
# ---------------------------------------------------------------------------

def test_relabelling_keeps_group_sizes_kb_cap_and_programs():
    """P·A·Pᵀ keeps the plan's group sizes and kb_cap, multiplies to
    P·A²·Pᵀ, and a second relabelling handed over as host CSR misses the
    plan cache but builds no new program."""
    relabel = workload.load("drives", "oneshot_product").relabel
    n = 3000
    indptr, indices = workload.pattern({"generator": "uniform", "structure_seed": 0}, n, 6.2)
    rng = np.random.default_rng(6)

    def host(perm):
        ip, ix = relabel(indptr, indices, perm)
        return CSR(ip, ix, rng.uniform(0.1, 1.1, len(ix)).astype(np.float32), (n, n))

    base = CSR(indptr, indices, np.ones(len(indices), np.float32), (n, n))
    perms = [rng.permutation(n) for _ in range(2)]
    a1, a2 = host(perms[0]), host(perms[1])
    plans = [group_rows(m, m) for m in (base, a1, a2)]
    assert plans[1].group_sizes == plans[2].group_sizes == plans[0].group_sizes
    kb = {int(np.diff(np.asarray(m.indptr)).max()) for m in (base, a1, a2)}
    assert len(kb) == 1

    cache = executor.PlanCache()
    c1 = spgemm(a1, a1, plan=cache).c
    built = executor.cache_stats()["misses"]
    c2 = spgemm(a2, a2, plan=cache).c
    assert executor.cache_stats()["misses"] == built
    assert cache.stats()["misses"] == 2

    for a, c in ((a1, c1), (a2, c2)):
        m = sp.csr_matrix((a.data, a.indices, a.indptr), shape=(n, n))
        nnz = int(np.asarray(c.indptr)[-1])
        parts = (np.asarray(c.data)[:nnz], np.asarray(c.indices)[:nnz], np.asarray(c.indptr))
        assert abs(sp.csr_matrix(parts, shape=(n, n)) - m @ m).max() < 1e-5
    # relabelled back by the inverse permutation, the pattern is the base's
    inverse = np.argsort(perms[0])
    back = relabel(np.asarray(a1.indptr), np.asarray(a1.indices), inverse)
    np.testing.assert_array_equal(back[0], indptr)
    np.testing.assert_array_equal(back[1], indices)


def test_sage_step_counts_from_the_problem_shapes():
    """Model FLOPs and useful gather bytes of one proteins step: both
    paths' matmuls (forward, weight gradient, and past layer 0 the input
    gradient), the aggregations' needed columns, layer 0's 8 features and
    past it 16 (value, id) pairs a stored entry."""
    from sage_counts import sage_step_counts

    n, nnz = CONFIG["nodes"], PROTEINS_ENTRIES
    c = sage_step_counts(n, nnz, 8, 256, 112, 3, 16)
    matmul = 2 * 2 * n * (8 * 256 * 2 + 256 * 256 * 3 + 256 * 112 * 3)
    assert c["matmul_flops"] == matmul == 152_000_593_920
    assert c["flops"] == matmul + 2 * nnz * (8 + 2 * 16 * 2)
    assert c["gather_bytes"] == nnz * (8 * 4 + 2 * 16 * 8)
