"""plan_host_s.product: host seconds per product in ``spgemm.plan`` spans
(the pattern fingerprint and the ``PlanCache`` lookup, and Alg. 1 and the
Table-I binning on a miss); nothing where the trace holds no such span."""

import spans


def read(ctx):
    found = spans.named(spans.window_spans(), "spgemm.plan")
    return sum(s.seconds for s in found) / ctx["items"] if found else None
