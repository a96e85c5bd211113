"""Device time (ms) per train step of the optimizer (AdamW): ops under
``step/optimizer``."""

from bench import reduce


def read(ctx):
    return reduce.scope_ms_per_unit(ctx, "step/optimizer")
