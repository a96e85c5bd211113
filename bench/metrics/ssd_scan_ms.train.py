"""Device time (ms) per train step of the SSD chunked scan, forward and
backward: ops under ``ssd/chunk`` (the intra-chunk dual form) or
``ssd/state`` (chunk-boundary states, the inter-chunk scan, their
contribution)."""

from bench import scopes


def read(ctx):
    return scopes.ms_per_unit(ctx, scopes.ssd_scan)
