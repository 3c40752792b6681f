"""Paper applications: MCL, Graph Contraction, GNN+TopK training."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import (
    rmat_graph, uniform_graph, mcl, graph_contraction,
    GNNConfig, init_gnn, gnn_forward, train_gnn,
)
from repro.apps.graph_contraction import label_matrix
from repro.apps.gnn import normalize_adjacency
from repro.sparse.formats import csr_to_dense, csr_from_dense
from repro.sparse.ops import csr_column_sums


def test_generators_shapes_and_stats():
    g = rmat_graph(256, 8.0, seed=1)
    assert g.shape == (256, 256)
    nnz = int(np.asarray(g.nnz))
    assert 256 * 4 < nnz <= 256 * 8  # dedup/self-loop removal shrinks a bit
    u = uniform_graph(256, 4.0, seed=1)
    deg = np.asarray(u.row_nnz())
    assert deg.max() < 20  # flat distribution


# ---------------------------------------------------------------------------
# Graph contraction — Algorithm 7 invariants
# ---------------------------------------------------------------------------

def test_contraction_matches_dense_oracle():
    rng = np.random.default_rng(0)
    n, m = 30, 5
    g = uniform_graph(n, 3.0, seed=2)
    labels = rng.integers(0, m, n)
    c, infos = graph_contraction(g, labels)
    s_dense = np.zeros((m, n), np.float32)
    s_dense[labels, np.arange(n)] = 1.0
    g_dense = np.asarray(csr_to_dense(g))
    expect = s_dense @ g_dense @ s_dense.T
    np.testing.assert_allclose(np.asarray(csr_to_dense(c)), expect,
                               rtol=1e-4, atol=1e-4)
    assert c.shape == (m, m)
    assert len(infos) == 2


def test_contraction_preserves_total_weight():
    """Merging nodes must conserve Σ edge weights (S has exactly one 1/col)."""
    g = rmat_graph(64, 4.0, seed=3)
    labels = np.random.default_rng(1).integers(0, 7, 64)
    c, _ = graph_contraction(g, labels)
    total_g = float(np.asarray(csr_to_dense(g)).sum())
    total_c = float(np.asarray(csr_to_dense(c)).sum())
    np.testing.assert_allclose(total_c, total_g, rtol=1e-4)


def test_label_matrix_structure():
    labels = np.array([2, 0, 1, 0])
    s = label_matrix(labels)
    d = np.asarray(csr_to_dense(s))
    assert d.shape == (3, 4)
    np.testing.assert_array_equal(d.sum(axis=0), np.ones(4))


# ---------------------------------------------------------------------------
# MCL — Algorithm 6 invariants
# ---------------------------------------------------------------------------

def test_mcl_two_blocks():
    """Two dense blocks + one bridge edge -> exactly two clusters."""
    n = 16
    x = np.zeros((n, n), np.float32)
    x[:8, :8] = 1.0
    x[8:, 8:] = 1.0
    np.fill_diagonal(x, 0)
    x[7, 8] = x[8, 7] = 0.1  # weak bridge
    g = csr_from_dense(x)
    res = mcl(g, e=2, r=2.0, k=16, max_iters=12)
    labels = res.clusters
    assert len(np.unique(labels[:8])) == 1
    assert len(np.unique(labels[8:])) == 1
    assert labels[0] != labels[8]


def test_mcl_column_stochastic_invariant():
    """After every iteration the matrix stays column-stochastic."""
    g = rmat_graph(48, 3.0, seed=4)
    res = mcl(g, e=2, r=2.0, k=16, max_iters=3, tol=0.0)
    s = np.asarray(csr_column_sums(res.matrix))
    nonzero = s > 1e-9
    np.testing.assert_allclose(s[nonzero], 1.0, rtol=1e-4)


def test_mcl_runs_spgemm_per_iteration():
    g = rmat_graph(32, 3.0, seed=5)
    res = mcl(g, e=2, max_iters=3, tol=0.0)
    assert len(res.spgemm_info) == res.n_iterations
    for info in res.spgemm_info:
        assert info["flops"] == 2 * info["intermediate_products"]


# ---------------------------------------------------------------------------
# GNN + TopK (Eq. 1–3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["gcn", "gin", "sage"])
def test_gnn_forward_shapes(arch):
    g = rmat_graph(64, 4.0, seed=6)
    a = normalize_adjacency(g)
    cfg = GNNConfig(arch=arch, d_in=16, d_hidden=32, n_classes=5, topk=8)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    x = np.random.default_rng(0).standard_normal((64, 16)).astype(np.float32)
    logits = gnn_forward(cfg, params, a, jnp.asarray(x))
    assert logits.shape == (64, 5)
    assert not np.isnan(np.asarray(logits)).any()


@pytest.mark.parametrize("arch", ["gcn", "gin", "sage"])
def test_gnn_training_loss_decreases(arch):
    rng = np.random.default_rng(7)
    n = 96
    g = rmat_graph(n, 5.0, seed=7)
    a = normalize_adjacency(g)
    x = rng.standard_normal((n, 16)).astype(np.float32)
    labels = rng.integers(0, 4, n)
    cfg = GNNConfig(arch=arch, d_in=16, d_hidden=32, n_classes=4, topk=8)
    _, hist = train_gnn(cfg, a, x, labels, n_steps=25, lr=5e-3)
    assert hist[-1] < hist[0] * 0.9, hist


@pytest.mark.parametrize("arch", ["gcn", "gin", "sage"])
def test_gnn_minibatch_training_runs_and_amortizes(arch):
    """Mini-batch path: finite losses, and epoch-revisited batches hit the
    shared PlanCache (the sampler's probability patterns repeat)."""
    from repro.apps.gnn import train_gnn_minibatch

    rng = np.random.default_rng(11)
    n = 48
    g = normalize_adjacency(rmat_graph(n, 4.0, seed=11))
    x = rng.standard_normal((n, 8)).astype(np.float32)
    labels = rng.integers(0, 3, n)
    cfg = GNNConfig(arch=arch, n_layers=2, d_in=8, d_hidden=16,
                    n_classes=3, topk=8)
    params, hist, stats = train_gnn_minibatch(
        cfg, g, x, labels, batch_size=16, n_epochs=2, fanout=3, seed=2)
    assert len(hist) == 2 * 3  # 2 epochs × ceil(48/16) batches
    assert np.isfinite(hist).all()
    assert stats["plan_cache_hits"] > 0, stats
    for k, v in params.items():
        assert np.isfinite(np.asarray(v)).all(), k


def test_gnn_minibatch_forward_shapes_and_weight_ensemble():
    from repro.apps.gnn import gnn_forward_minibatch, init_gnn
    from repro.apps.sampling import bulk_sample

    rng = np.random.default_rng(12)
    n = 64
    g = normalize_adjacency(rmat_graph(n, 4.0, seed=12))
    cfg = GNNConfig(arch="sage", n_layers=2, d_in=8, d_hidden=16,
                    n_classes=4, topk=8)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    x = jnp.asarray(rng.standard_normal((n, 8)).astype(np.float32))
    batch = np.asarray([3, 7, 11])
    adjs, frontiers = bulk_sample(g, batch, fanout=2, n_layers=2, seed=4)
    logits = gnn_forward_minibatch(cfg, params, adjs, frontiers, x)
    assert logits.shape == (len(batch), 4)
    assert np.isfinite(np.asarray(logits)).all()
    # the edge-weight ensemble path produces the same shapes
    nnz = int(np.asarray(g.indptr)[-1])
    ws = np.stack([np.asarray(g.data)[:nnz]] * 2)
    adjs2, frontiers2 = bulk_sample(g, batch, fanout=2, n_layers=2, seed=4,
                                    weight_sets=ws)
    logits2 = gnn_forward_minibatch(cfg, params, adjs2, frontiers2, x)
    np.testing.assert_allclose(np.asarray(logits2), np.asarray(logits),
                               rtol=1e-5, atol=1e-5)


def test_gnn_topk_vs_dense_agree_when_k_full():
    """k = d_hidden makes TopK the identity: sparse path == dense path."""
    rng = np.random.default_rng(8)
    n = 48
    g = rmat_graph(n, 4.0, seed=8)
    a = normalize_adjacency(g)
    x = jnp.asarray(rng.standard_normal((n, 12)).astype(np.float32))
    key = jax.random.PRNGKey(3)
    cfg_s = GNNConfig(arch="gcn", d_in=12, d_hidden=24, n_classes=3,
                      topk=24, sparse_mode="topk")
    cfg_d = dataclasses_replace(cfg_s, sparse_mode="dense")
    params = init_gnn(cfg_s, key)
    ls = gnn_forward(cfg_s, params, a, x)
    ld = gnn_forward(cfg_d, params, a, x)
    np.testing.assert_allclose(np.asarray(ls), np.asarray(ld), rtol=1e-5)


def dataclasses_replace(cfg, **kw):
    import dataclasses
    return dataclasses.replace(cfg, **kw)


def test_mcl_drop_zeros_compacts_pruned_entries():
    from repro.apps.markov_clustering import drop_zeros

    x = np.array([[0.5, 0.0, 0.25], [0.0, 0.0, 1.0], [2.0, 0.0, 0.0]],
                 np.float32)
    g = csr_from_dense(x)
    g = type(g)(g.indptr, g.indices, g.data.at[0].set(0.0), g.shape)
    c = drop_zeros(g)
    assert int(np.asarray(c.indptr)[-1]) == 3
    assert c.indices.shape[0] == 4  # pow2-quantized capacity
    x[0, 0] = 0.0
    np.testing.assert_array_equal(np.asarray(csr_to_dense(c)), x)


def test_mcl_iterates_on_the_pruned_structure():
    """Pruned entries leave the structure, so the next expansion only
    multiplies what survived: every stored entry of the result is nonzero
    and each column keeps at most k of them."""
    g = rmat_graph(64, 4.0, seed=1)
    res = mcl(g, e=2, r=2.0, k=4, max_iters=3, tol=0.0)
    nnz = int(np.asarray(res.matrix.indptr)[-1])
    data = np.asarray(res.matrix.data)[:nnz]
    assert np.all(data != 0)
    cols = np.asarray(res.matrix.indices)[:nnz]
    assert np.bincount(cols, minlength=64).max() <= 4
