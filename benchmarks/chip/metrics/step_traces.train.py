"""step_traces.train: ``gnn.trace`` spans in the traced window, one each time
JAX traces the training step's Python body; nothing where the trace holds
no ``gnn.train`` span."""

import spans


def read(ctx):
    found = spans.window_spans()
    if not spans.named(found, "gnn.train"):
        return None
    return len(spans.named(found, "gnn.trace"))
