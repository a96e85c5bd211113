"""Device time (ms) per train step of the per-worker backward: ops under
``step/grad`` whose ``op_name`` holds ``transpose(``."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_unit(ctx, scopes.backward)
