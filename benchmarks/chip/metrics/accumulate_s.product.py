"""accumulate_s.product: device seconds per product of the sort engine's
Table-I programs, the ops whose program is ``jit_spgemm_allocate_t<cap>`` or
``jit_spgemm_accumulate_t<cap>``; nothing where the trace holds no program of
those names."""

PROGRAMS = ("jit_spgemm_allocate_t", "jit_spgemm_accumulate_t")


def read(ctx):
    seconds = sum(s for op, s in ctx["trace"].ops.items() if op.startswith(PROGRAMS))
    return seconds / ctx["items"] if seconds > 0 else None
