"""aia_gather_roofline.train: the useful bytes of the forward aggregations'
row gather (per stored entry of A_hat, layer 0's dense input row, and past
it the k kept entries of a TopK row as value and column id) at the chip's
HBM bandwidth, over the device time of the AIA gather kernels, in percent."""

from counts import gather_roofline


def read(ctx):
    return gather_roofline(ctx)
