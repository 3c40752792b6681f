"""aia_gather_roofline.product: the useful bytes of the B-row gather (a
column id and a value of B per intermediate product) at the chip's HBM
bandwidth, over the device time of the AIA gather kernels, in percent."""

from counts import gather_roofline


def read(ctx):
    return gather_roofline(ctx)
