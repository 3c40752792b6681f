"""Plain reference for full-batch GCN training on ogbn-arxiv, and the
comparison that decides ``correct``.

The model, as the configuration states it: per layer
h <- A_hat . TopK(h) . W (layer 0 aggregates the dense input features),
ReLU between layers, mean cross-entropy over every node, gradients clipped
to global norm 1, then AdamW.  TopK keeps the k largest |h| of each row,
and its gradient flows through the kept entries only.  The initial weights
are drawn from the seed by the configuration's rule: W_l ~ N(0, 1/d_in),
with keys split from ``PRNGKey(seed)`` three at a time per layer.

Straightforward ``jax.numpy`` in float32 with every matmul at ``HIGHEST``
precision; the aggregation is a gather and a segment sum over the stored
entries of A_hat.  It imports nothing of the program.  The control is the
same computation with each matmul one bfloat16 pass: both operands rounded
to bfloat16, the products accumulated in float32, as the chip's matrix unit
does at default precision.  That is the step a later change would be
tempted to take (dropping the configuration's ``highest``), written out so
that it means the same on every backend.  ``high`` (three passes), the step
just below ``highest``, fails none of the numbers below (PERF.md gives its
readings).

Three numbers are compared (PERF.md gives the readings each limit was set
from):

* ``step1_loss_gap``: the relative gap between the program's first loss
  and the reference's.  The first step's forward pass runs every layer the
  cell names (the AIA gather, TopK, the matmuls) on the initial weights and
  reads one or two float32 ulps on every seed.  Later steps' losses swing
  from seed to seed: Adam scales each element's first updates to about
  ±lr, so a gradient element near zero whose sign round-off decides moves
  its weight by 2·lr either way;
* ``loss_gap``: the largest relative gap between the program's loss and
  the reference's, over every step of the window;
* ``change_gap``: over the weight matrices, the worst gap between the norm
  of the program's change of the matrix over the window and the
  reference's, relative to the reference's change of that matrix or of the
  median matrix, whichever is larger.  A matrix whose first gradient in the
  reference is under a thousandth of the median matrix's is left out: Adam
  moves it by round-off alone.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"step1_loss_gap": 1e-6, "loss_gap": 2e-3, "change_gap": 1.5e-2}


def init_params(cfg, seed: int):
    """The initial weights, drawn from the seed by the configuration's rule."""
    import jax
    import jax.numpy as jnp

    dims = [cfg["features"]] + [cfg["hidden"]] * (cfg["layers"] - 1) + [cfg["classes"]]
    key = jax.random.PRNGKey(seed)
    params = {}
    for layer in range(cfg["layers"]):
        key, k1, _ = jax.random.split(key, 3)
        w = jax.random.normal(k1, (dims[layer], dims[layer + 1])) / np.sqrt(dims[layer])
        params[f"w{layer}"] = w.astype(jnp.float32)
    return params


def _dot_highest(a, b):
    import jax
    import jax.numpy as jnp

    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _dot_bf16(a, b):
    """One bfloat16 pass: both operands rounded to bfloat16 with
    ``reduce_precision`` (which no backend may skip as excess precision),
    then multiplied exactly and accumulated in float32."""
    import jax

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    return _dot_highest(bf16(a), bf16(b))


def _train(cfg, graph, x, labels, seed: int, steps: int, dot):
    """``steps`` training steps from the seed's initial weights; returns
    (initial weights, final weights, losses, first gradient norms), on the
    host."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    opt = cfg["optimizer"]
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    layers, k = cfg["layers"], cfg["topk"]

    def aggregate(graph, h):
        rows, cols, vals = graph
        return jax.ops.segment_sum(
            vals[:, None] * h[cols], rows, num_segments=n, indices_are_sorted=True
        )

    def topk(h):
        _, idx = jax.lax.top_k(jnp.abs(h), min(k, h.shape[1]))
        mask = jnp.zeros(h.shape, bool).at[jnp.arange(h.shape[0])[:, None], idx].set(True)
        return jnp.where(mask, h, 0.0)

    def loss_fn(params, graph, x, labels):
        h = x
        for layer in range(layers):
            agg = aggregate(graph, h if layer == 0 else topk(h))
            h = dot(agg, params[f"w{layer}"])
            if layer < layers - 1:
                h = jax.nn.relu(h)
        logp = jax.nn.log_softmax(h, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))

    # The graph and the data are arguments: as constants they would be
    # folded into the program.
    @jax.jit
    def step(params, m, v, t, graph, x, labels):
        loss, g = jax.value_and_grad(loss_fn)(params, graph, x, labels)
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

    graph = tuple(jnp.asarray(a) for a in graph)
    p0 = init_params(cfg, seed)
    params = p0
    m = {name: jnp.zeros_like(w) for name, w in p0.items()}
    v = {name: jnp.zeros_like(w) for name, w in p0.items()}
    t = jnp.zeros((), jnp.float32)
    losses, first_gnorms = [], None
    for _ in range(steps):
        params, m, v, t, loss, gnorms = step(params, m, v, t, graph, x, labels)
        losses.append(float(loss))
        if first_gnorms is None:
            first_gnorms = {name: float(val) for name, val in gnorms.items()}
    host = lambda tree: {name: np.asarray(w) for name, w in tree.items()}  # noqa: E731
    return host(p0), host(params), losses, first_gnorms


def reference(cfg, graph, x, labels, seed: int, steps: int):
    """The reference's training run, float32 at highest precision."""
    return _train(cfg, graph, x, labels, seed, steps, _dot_highest)


def control(cfg, graph, x, labels, seed: int, steps: int):
    """The reference with each matmul one bfloat16 pass."""
    return _train(cfg, graph, x, labels, seed, steps, _dot_bf16)


def compare(losses, params, ref) -> dict:
    """The numbers compared for one training run, by name.  ``losses`` and
    ``params`` are the program's; ``ref`` is what ``reference`` returned."""
    p0, p_ref, ref_losses, gnorms = ref
    if len(losses) != len(ref_losses) or set(params) != set(p_ref):
        return dict.fromkeys(LIMITS, float("inf"))
    gaps = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
    median_g = float(np.median(list(gnorms.values())))
    kept = [name for name in p_ref if gnorms[name] >= 1e-3 * median_g]
    ref_change = {name: float(np.linalg.norm(p_ref[name] - p0[name])) for name in kept}
    floor = float(np.median(list(ref_change.values())))
    change_gap = 0.0
    for name in kept:
        got = float(np.linalg.norm(np.asarray(params[name], np.float32) - p0[name]))
        change_gap = max(change_gap, abs(got - ref_change[name]) / max(ref_change[name], floor))
    if not all(np.isfinite(losses)):
        return dict.fromkeys(LIMITS, float("inf"))
    return {
        "step1_loss_gap": float(gaps[0]),
        "loss_gap": float(max(gaps)),
        "change_gap": float(change_gap),
    }
