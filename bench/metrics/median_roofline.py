"""Roofline share (%) of the ``cwise_median`` kernel: the bucket rows read
once and the median row written once."""

from bench import reduce


def read(ctx):
    return reduce.kernel_roofline(ctx, "cwise_median", "median")
