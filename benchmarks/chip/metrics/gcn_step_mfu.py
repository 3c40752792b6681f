"""gcn_step_mfu: model FLOPs of the training steps of the traced window
(dense matmuls forward and backward, and the aggregations' required work;
nothing recomputed) over its seconds, as a share of the chip's peak."""


def read(ctx):
    flops = ctx["counts"]["flops"] * ctx["items"]
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"]["flops_per_s"]
