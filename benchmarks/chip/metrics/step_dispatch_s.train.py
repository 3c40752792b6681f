"""step_dispatch_s.train: host seconds per step in ``gnn.step`` spans, the
dispatch of each training step (the first also traces the step and loads
its executable); nothing where the trace holds no such span."""

import spans


def read(ctx):
    found = spans.named(spans.window_spans(), "gnn.step")
    return sum(s.seconds for s in found) / ctx["items"] if found else None
