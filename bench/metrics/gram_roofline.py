"""Roofline share (%) of the ``pairwise_gram`` kernel: the W rows read once."""

from bench import reduce


def read(ctx):
    return reduce.kernel_roofline(ctx, "pairwise_gram", "gram")
