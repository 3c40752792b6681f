"""Plain reference for full-batch GraphSAGE training on ogbn-proteins, and
the comparison that decides ``correct``.

The model, as the configuration states it (OGB's ``SAGEConv`` with mean
aggregation): per layer
h <- (D^-1 A) . TopK(h) . W_l + b_l + h . W_r, where layer 0 aggregates the
dense input features and the self path always takes the whole h; ReLU
between layers; binary cross-entropy with logits, the mean over every node
and task; gradients clipped to global norm 1, then AdamW.  TopK keeps the k
largest |h| of each row, and its gradient flows through the kept entries
only.  The initial weights are drawn from the seed by the program's rule:
per layer, in order, ``key, k1, k2 = split(key, 3)`` from
``PRNGKey(seed)``; W_l ~ N(0, 1/d_in) from k1, W_r ~ N(0, 1/d_in) from k2,
b_l = 0.

Straightforward ``jax.numpy`` in float32 with every matmul at ``HIGHEST``
precision.  The aggregation is a gather and a segment sum over the stored
entries of D^-1 A, taken ``BLOCK`` entries at a time in a ``scan`` whose
body is under ``jax.checkpoint``: neither pass holds a row of h per stored
entry (79.1 M rows of 256 floats would be 81 GB).  It imports nothing of
the program.  The control is the same computation with each matmul one
bfloat16 pass, as the GCN reference's control (``ogbn-arxiv-gcn.py``, whose
matmul helpers and comparison this file shares).

Three numbers are compared, as for the GCN (PERF.md gives the readings each
limit was set from):

* ``step1_loss_gap``: the relative gap between the program's first loss
  and the reference's: the first forward pass on the initial weights;
* ``loss_gap``: the largest relative gap between the program's loss and
  the reference's, over every step of the window;
* ``change_gap``: over ``w``, ``w_self`` and ``b`` of every layer, the
  worst gap between the norm of the program's change of the parameter over
  the window and the reference's, relative to the reference's change of
  that parameter or of the median one, whichever is larger.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

import workload

LIMITS = {"step1_loss_gap": 2e-6, "loss_gap": 5e-6, "change_gap": 2e-5}
BLOCK = 1 << 21  # stored entries per aggregation block (2 GiB of 256-float rows)

_gcn = workload.load_path(Path(__file__).with_name("ogbn-arxiv-gcn.py"), "reference_gcn_shared")


def init_params(cfg, seed: int):
    """The initial parameters, drawn from the seed by the configuration's
    rule."""
    import jax
    import jax.numpy as jnp

    dims = [cfg["features"]] + [cfg["hidden"]] * (cfg["layers"] - 1) + [cfg["tasks"]]
    key = jax.random.PRNGKey(seed)
    params = {}
    for layer in range(cfg["layers"]):
        key, k1, k2 = jax.random.split(key, 3)
        shape = (dims[layer], dims[layer + 1])
        params[f"w{layer}"] = jax.random.normal(k1, shape) / np.sqrt(dims[layer])
        params[f"w_self{layer}"] = jax.random.normal(k2, shape) / np.sqrt(dims[layer])
        params[f"b{layer}"] = jnp.zeros((dims[layer + 1],), jnp.float32)
    return params


def blocks_of(graph, n: int, block: int):
    """(rows, cols, vals) of the stored entries as (blocks, entries) arrays;
    the last block is padded with entries of row ``n``, which the segment
    sum drops."""
    rows, cols, vals = (np.asarray(a) for a in graph)
    nnz = len(rows)
    size = min(block, max(nnz, 1))
    total = -(-nnz // size) * size
    pad = total - nnz
    return (
        np.concatenate([rows, np.full(pad, n, rows.dtype)]).reshape(-1, size),
        np.concatenate([cols, np.zeros(pad, cols.dtype)]).reshape(-1, size),
        np.concatenate([vals, np.zeros(pad, vals.dtype)]).reshape(-1, size),
    )


def _train(cfg, graph, x, labels, seed: int, steps: int, dot):
    """``steps`` training steps from the seed's initial parameters; returns
    (initial parameters, final parameters, losses, first gradient norms),
    on the host."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    opt = cfg["optimizer"]
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    layers, k = cfg["layers"], cfg["topk"]

    def aggregate(blocks, h):
        @jax.checkpoint
        def block_sum(h, rows, cols, vals):
            return jax.ops.segment_sum(vals[:, None] * h[cols], rows, num_segments=n)

        def body(acc, blk):
            return acc + block_sum(h, *blk), None

        out, _ = jax.lax.scan(body, jnp.zeros((n, h.shape[1]), h.dtype), blocks)
        return out

    def topk(h):
        _, idx = jax.lax.top_k(jnp.abs(h), min(k, h.shape[1]))
        mask = jnp.zeros(h.shape, bool).at[jnp.arange(h.shape[0])[:, None], idx].set(True)
        return jnp.where(mask, h, 0.0)

    def loss_fn(params, blocks, x, labels):
        h = x
        for layer in range(layers):
            agg = aggregate(blocks, h if layer == 0 else topk(h))
            h = (
                dot(agg, params[f"w{layer}"])
                + params[f"b{layer}"]
                + dot(h, params[f"w_self{layer}"])
            )
            if layer < layers - 1:
                h = jax.nn.relu(h)
        bce = jnp.maximum(h, 0) - h * labels + jnp.log1p(jnp.exp(-jnp.abs(h)))
        return jnp.mean(bce)

    # The graph and the data are arguments: as constants they would be
    # folded into the program.
    @jax.jit
    def step(params, m, v, t, blocks, x, labels):
        loss, g = jax.value_and_grad(loss_fn)(params, blocks, x, labels)
        gnorms = {name: jnp.sqrt(jnp.sum(gl * gl)) for name, gl in g.items()}
        total = jnp.sqrt(sum(jnp.sum(gl * gl) for gl in g.values()))
        scale = jnp.minimum(1.0, opt["clip_global_norm"] / (total + 1e-9))
        t = t + 1.0
        new_p, new_m, new_v = {}, {}, {}
        for name in params:
            gl = g[name] * scale
            new_m[name] = b1 * m[name] + (1 - b1) * gl
            new_v[name] = b2 * v[name] + (1 - b2) * gl * gl
            mhat = new_m[name] / (1 - b1**t)
            vhat = new_v[name] / (1 - b2**t)
            upd = mhat / (jnp.sqrt(vhat) + eps) + opt["weight_decay"] * params[name]
            new_p[name] = params[name] - lr * upd
        return new_p, new_m, new_v, t, loss, gnorms

    blocks = tuple(jnp.asarray(a) for a in blocks_of(graph, n, BLOCK))
    p0 = init_params(cfg, seed)
    params = p0
    m = {name: jnp.zeros_like(w) for name, w in p0.items()}
    v = {name: jnp.zeros_like(w) for name, w in p0.items()}
    t = jnp.zeros((), jnp.float32)
    losses, first_gnorms = [], None
    for _ in range(steps):
        params, m, v, t, loss, gnorms = step(params, m, v, t, blocks, x, labels)
        losses.append(float(loss))
        if first_gnorms is None:
            first_gnorms = {name: float(val) for name, val in gnorms.items()}
    host = lambda tree: {name: np.asarray(w) for name, w in tree.items()}  # noqa: E731
    return host(p0), host(params), losses, first_gnorms


def reference(cfg, graph, x, labels, seed: int, steps: int):
    """The reference's training run, float32 at highest precision."""
    return _train(cfg, graph, x, labels, seed, steps, _gcn._dot_highest)


def control(cfg, graph, x, labels, seed: int, steps: int):
    """The reference with each matmul one bfloat16 pass."""
    return _train(cfg, graph, x, labels, seed, steps, _gcn._dot_bf16)


def compare(losses, params, ref) -> dict:
    """The numbers compared for one training run, by name (the GCN's
    comparison, over every parameter)."""
    return _gcn.compare(losses, params, ref)
