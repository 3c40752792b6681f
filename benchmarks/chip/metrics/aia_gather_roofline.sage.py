"""aia_gather_roofline.sage: the useful bytes of the forward aggregations'
row gather (per stored entry of D^-1 A, layer 0's 8 input features, and
past it the k kept entries of a TopK row as value and column id) at the
chip's HBM bandwidth, over the device time of the AIA gather kernels, in
percent."""

from counts import gather_roofline


def read(ctx):
    return gather_roofline(ctx)
