"""Device time (ms) per train step of the worker-momentum update: ops under
``step/momentum``."""

from bench import reduce


def read(ctx):
    return reduce.scope_ms_per_unit(ctx, "step/momentum")
