"""operand_builds.product: ``spgemm.operands.build`` spans (B-side ELL builds
and placements, one per ``OperandCache`` miss) per product of the traced
window; nothing where the trace holds no ``spgemm`` span."""

import spans


def read(ctx):
    found = spans.window_spans()
    if not spans.named(found, "spgemm"):
        return None
    return len(spans.named(found, "spgemm.operands.build")) / ctx["items"]
