"""GNN full-batch training with TopK structured pruning (paper §V-C, Eq. 1–3).

Three architectures (GCN, GIN, GraphSAGE — the paper's Fig. 10/11 set), each
with a pruning layer that sparsifies activations so the aggregation
``A · TopK(X) · W`` is an SpGEMM instead of an SpMM.  The TopK backward is
the paper's Eq. (3) winner-take-all mask (``topk_rows_st`` custom VJP).

``sparse_mode``:
  * "topk"  — Eq. (1): aggregation over TopK-masked features (the paper's
              AIA-accelerated path; the gather inside ``csr_spmm`` is the
              two-level indirection AIA serves).
  * "dense" — the cuSPARSE-role baseline: dense Â @ X @ W.

Mini-batch path (``train_gnn_minibatch``): each step trains on a
bulk-sampled subgraph chain from ``apps.sampling.bulk_sample`` — the
SpGEMM-expressed sampler whose per-batch probability patterns repeat every
epoch.  A shared ``PlanCache`` therefore amortizes the sampler's
Algorithm-1 setups across epochs, and an optional edge-weight ensemble
(``weight_sets``) routes the probability products through the *batched*
executor (one plan, many same-pattern value sets).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Literal, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import adamw, apply_updates, clip_by_global_norm
from repro.sparse.formats import CSR
from repro.sparse.ops import csr_spmm
from repro.sparse.topk import topk_rows_st


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    arch: Literal["gcn", "gin", "sage"] = "gcn"
    n_layers: int = 2
    d_in: int = 64
    d_hidden: int = 64
    n_classes: int = 7
    topk: int = 16  # k of Eq. (1); <= d_hidden
    sparse_mode: Literal["topk", "dense"] = "topk"
    # How the aggregation's two-level indirect gather is served: "aia" uses
    # the scalar-prefetch Pallas kernels (paper's accelerated path), "xla"
    # the software-only baseline, "auto" picks by backend (AIA on TPU).
    gather: Literal["auto", "xla", "aia"] = "auto"


def normalize_adjacency(a: CSR) -> CSR:
    """Â = D^{-1/2} (A+I) D^{-1/2} for GCN (built host-side once)."""
    from repro.apps.markov_clustering import add_self_loops
    from repro.sparse.ops import csr_scale_rows, csr_scale_columns
    a = add_self_loops(a)
    deg = np.asarray(a.row_nnz()).astype(np.float32)
    dinv = jnp.asarray(1.0 / np.sqrt(np.maximum(deg, 1.0)))
    return csr_scale_columns(csr_scale_rows(a, dinv), dinv)


def init_gnn(cfg: GNNConfig, key) -> Dict:
    dims = [cfg.d_in] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    params = {}
    for layer in range(cfg.n_layers):
        key, k1, k2 = jax.random.split(key, 3)
        fan_in = dims[layer]
        w = jax.random.normal(k1, (fan_in, dims[layer + 1])) / np.sqrt(fan_in)
        params[f"w{layer}"] = w.astype(jnp.float32)
        if cfg.arch == "sage":
            params[f"w_self{layer}"] = (
                jax.random.normal(k2, (fan_in, dims[layer + 1])) / np.sqrt(fan_in)
            ).astype(jnp.float32)
        if cfg.arch == "gin":
            params[f"eps{layer}"] = jnp.zeros((), jnp.float32)
    return params


def _aggregate(a: CSR, x: jax.Array, mode: str, k: int,
               gather: str = "auto", mesh=None) -> jax.Array:
    """A · TopK(X) — Eq. (1)'s sparse aggregation (or dense baseline)."""
    if mode == "topk":
        xs = topk_rows_st(x, k)  # Eq. (2) fwd, Eq. (3) bwd
        return csr_spmm(a, xs, gather=gather, mesh=mesh)
    return csr_spmm(a, x, gather=gather, mesh=mesh)


def gnn_forward(cfg: GNNConfig, params: Dict, a: CSR, x: jax.Array,
                mesh=None) -> jax.Array:
    """Forward pass; ``mesh`` row-shards every layer's aggregation so GSPMD
    splits the SpMM across the mesh's first axis."""
    h = x
    for layer in range(cfg.n_layers):
        k = min(cfg.topk, h.shape[1])
        mode = cfg.sparse_mode if layer > 0 else "dense"  # input feats stay dense
        agg = _aggregate(a, h, mode, k, gather=cfg.gather, mesh=mesh)
        if cfg.arch == "gcn":
            h = agg @ params[f"w{layer}"]
        elif cfg.arch == "gin":
            h = ((1.0 + params[f"eps{layer}"]) * h + agg) @ params[f"w{layer}"]
        else:  # sage: self + mean-ish neighbor path
            h = h @ params[f"w_self{layer}"] + agg @ params[f"w{layer}"]
        if layer < cfg.n_layers - 1:
            h = jax.nn.relu(h)
    return h  # logits


def _loss_fn(cfg, params, a, x, labels, mask, mesh=None):
    logits = gnn_forward(cfg, params, a, x, mesh=mesh)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def train_gnn(
    cfg: GNNConfig,
    a: CSR,
    x: np.ndarray,
    labels: np.ndarray,
    n_steps: int = 30,
    lr: float = 1e-2,
    seed: int = 0,
    mesh=None,
) -> Tuple[Dict, List[float]]:
    """Full-batch training loop; returns (params, loss history).

    ``mesh`` row-shards the per-layer aggregations (forward and backward)
    over the mesh's first axis via GSPMD sharding constraints.
    """
    with jax.profiler.TraceAnnotation("gnn.train"):
        with jax.profiler.TraceAnnotation("gnn.init"):
            key = jax.random.PRNGKey(seed)
            params = init_gnn(cfg, key)
            opt = adamw(lr, weight_decay=0.0)
            opt_state = opt.init(params)
        x = jnp.asarray(x)
        labels = jnp.asarray(labels)
        mask = jnp.ones(labels.shape[0], jnp.float32)

        # The graph and features are arguments, not closed-over constants: a
        # constant of this size would be folded into the program the
        # compiler sees.
        @jax.jit
        def step(params, opt_state, a, x, labels, mask):
            # a step's Python body runs only while JAX traces it
            with jax.profiler.TraceAnnotation("gnn.trace"):
                loss, grads = jax.value_and_grad(
                    lambda p: _loss_fn(cfg, p, a, x, labels, mask, mesh=mesh)
                )(params)
                grads, _ = clip_by_global_norm(grads, 1.0)
                updates, opt_state = opt.update(grads, opt_state, params)
                return apply_updates(params, updates), opt_state, loss

        history = []
        for _ in range(n_steps):
            with jax.profiler.TraceAnnotation("gnn.step"):
                params, opt_state, loss = step(params, opt_state, a, x,
                                               labels, mask)
            with jax.profiler.TraceAnnotation("gnn.loss_read"):
                history.append(float(loss))
        return params, history


# ---------------------------------------------------------------------------
# Mini-batch path (bulk-sampled subgraphs, amortized SpGEMM planning)
# ---------------------------------------------------------------------------

def gnn_forward_minibatch(cfg: GNNConfig, params: Dict, adjs: Sequence[CSR],
                          frontiers: Sequence[np.ndarray], x: jax.Array,
                          mesh=None) -> jax.Array:
    """Layer-wise forward over one ``bulk_sample`` subgraph chain.

    ``adjs[l]`` maps frontier l+1's features onto frontier l
    (shape ``(|Q^l|, |Q^{l+1}|)``, frontiers[0] = the batch vertices).
    Features flow from the outermost frontier inwards: layer 0 (input
    features, dense mode as in the full-batch path) consumes the last
    adjacency, the final layer lands on the batch vertices.  Self features
    for GIN/SAGE are the restriction of the previous frontier's features
    (``Q^l ⊆ Q^{l+1}`` by construction, so it's a positional take).
    """
    n_layers = cfg.n_layers
    assert len(adjs) == n_layers, (len(adjs), n_layers)
    h = jnp.asarray(x)[jnp.asarray(frontiers[n_layers])]  # outermost feats
    for layer in range(n_layers):
        t = n_layers - 1 - layer  # chain position consumed by this layer
        a_l = adjs[t]
        rows, cols = np.asarray(frontiers[t]), np.asarray(frontiers[t + 1])
        k = min(cfg.topk, h.shape[1])
        mode = cfg.sparse_mode if layer > 0 else "dense"
        agg = _aggregate(a_l, h, mode, k, gather=cfg.gather, mesh=mesh)
        # cols is sorted-unique and contains rows: positional restriction
        h_self = h[jnp.asarray(np.searchsorted(cols, rows))]
        if cfg.arch == "gcn":
            h = agg @ params[f"w{layer}"]
        elif cfg.arch == "gin":
            h = ((1.0 + params[f"eps{layer}"]) * h_self + agg) @ params[f"w{layer}"]
        else:  # sage
            h = h_self @ params[f"w_self{layer}"] + agg @ params[f"w{layer}"]
        if layer < n_layers - 1:
            h = jax.nn.relu(h)
    return h  # logits for frontiers[0] (the batch vertices)


def train_gnn_minibatch(
    cfg: GNNConfig,
    a: CSR,
    x: np.ndarray,
    labels: np.ndarray,
    batch_size: int = 32,
    n_epochs: int = 2,
    fanout: int = 4,
    lr: float = 1e-2,
    seed: int = 0,
    engine: str = "sort",
    mesh=None,
    weight_sets: Optional[np.ndarray] = None,
    reuse_plan: bool = True,
    pipeline: str = "two_wave",
    sizing: str = "auto",
) -> Tuple[Dict, List[float], Dict[str, int]]:
    """Mini-batch training on ``bulk_sample`` subgraph chains.

    Returns (params, per-step loss history, amortization stats).  Each step
    samples a GraphSAGE-style L-layer neighborhood for its vertex batch
    (every SpGEMM in the chain goes through the plan-compiled executor,
    sharded under ``mesh=``) and trains on the sampled subgraphs.
    ``reuse_plan`` shares one ``PlanCache`` across all steps: each batch's
    neighborhood sampling is seeded per *batch* (not per epoch), so the
    same vertex batch re-appears every epoch with the same frontiers and
    the same probability pattern ``Q^l · A``, and from the second epoch on
    the sampler's planning cost is amortized away (hits reported in the
    stats).  ``weight_sets``
    forwards an edge-reweighting ensemble to ``bulk_sample``, turning each
    probability product into one batched SpGEMM.  ``pipeline`` forwards
    the executor sync structure to every sampling-chain SpGEMM, and
    ``sizing`` its output sizing (planned Alg. 1 bounds vs the measured
    uniqueCount sync).  ``a``
    should already be normalized as the architecture expects
    (e.g. ``normalize_adjacency``).  ``engine`` accepts any registered
    engine or ``"auto"`` (per-bin adaptive dispatch — epoch-revisited
    batches are the ``AutotuneCache``'s convergence case), validated up
    front.
    """
    from repro.apps.sampling import bulk_sample
    from repro.core import executor
    from repro.core.spgemm import PlanCache

    engine = executor.resolve_engine(engine)
    key = jax.random.PRNGKey(seed)
    params = init_gnn(cfg, key)
    opt = adamw(lr, weight_decay=0.0)
    opt_state = opt.init(params)
    x = jnp.asarray(x)
    labels_np = np.asarray(labels)
    n = a.n_rows
    order = np.random.default_rng(seed).permutation(n)
    batches = [np.sort(order[i: i + batch_size])
               for i in range(0, n, batch_size)]
    plan_cache = PlanCache(max_entries=256) if reuse_plan else None

    history: List[float] = []
    for epoch in range(n_epochs):
        for bi, batch in enumerate(batches):
            adjs, frontiers = bulk_sample(
                a, batch, fanout=fanout, n_layers=cfg.n_layers,
                # Per-batch (epoch-independent) seed: revisiting a batch
                # must reproduce its frontiers, or every deeper-layer
                # pattern re-fingerprints and the PlanCache never hits.
                seed=seed * 100_000 + bi,
                engine=engine, gather=cfg.gather, mesh=mesh,
                plan_cache=plan_cache, weight_sets=weight_sets,
                pipeline=pipeline, sizing=sizing,
            )
            y = jnp.asarray(labels_np[frontiers[0]])

            def loss_fn(p):
                logits = gnn_forward_minibatch(cfg, p, adjs, frontiers, x,
                                               mesh=mesh)
                logp = jax.nn.log_softmax(logits, axis=-1)
                return -jnp.mean(
                    jnp.take_along_axis(logp, y[:, None], axis=1))

            loss, grads = jax.value_and_grad(loss_fn)(params)
            grads, _ = clip_by_global_norm(grads, 1.0)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            history.append(float(loss))
    stats = {
        "plan_cache_hits": plan_cache.hits if plan_cache else 0,
        "plan_cache_misses": plan_cache.misses if plan_cache else 0,
    }
    return params, history, stats
