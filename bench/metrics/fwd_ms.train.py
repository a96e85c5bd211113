"""Device time (ms) per train step of the per-worker forward: ops under
``step/grad`` whose ``op_name`` holds no ``transpose(``, the embedding,
the layer scan and the unembed with its loss."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_unit(ctx, scopes.forward)
