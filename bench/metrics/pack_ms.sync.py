"""Device time (ms) per sync call of the pack (``GradPacker.pack`` and the
ingress): ops under ``telemetry/pack`` plus the sync program's ops that
carry no phase scope, which are XLA's layout copies for the pack's
reshapes (``reduce.pack_ops``)."""

from bench import reduce


def read(ctx):
    t_ns = reduce.busy_ns(ctx.trace, 0, lambda o: reduce.pack_ops(o, ctx.trace.programs))
    return t_ns / 1e6 / ctx.units if t_ns > 0 else None
