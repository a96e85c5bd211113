"""Roofline share (%) of the ``bucket_mix`` kernel: the W rows read once and
the mixed rows written once (RFA's combine, or CM's bucketing mix)."""

from bench import reduce


def read(ctx):
    return reduce.kernel_roofline(ctx, "bucket_mix", "mix")
