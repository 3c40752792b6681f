"""product_roofline: the least time one self-product's compulsory work
could take on the chip (A and B read once and C written once, indptr, ids
and values; 2 FLOP per intermediate product), over the measured seconds per
product of the traced window, in percent.  The bytes bound it."""

from counts import least_seconds


def read(ctx):
    c = ctx["counts"]
    least, _ = least_seconds(c["flops"], c["bytes"], ctx["peaks"])
    return 100.0 * least / (ctx["window_s"] / ctx["items"])
