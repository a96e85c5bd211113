"""Share (%) of the device's busy time in the train step spent in the robust
sync: ops under the sync's ``telemetry/*`` name scopes (pack, gram, coeff,
combine, mix, kernel, unpack) over all ops, averaged over the chips."""

from bench import reduce


def read(ctx):
    busy = reduce.mean_busy_ns(ctx.trace)
    sync = reduce.mean_busy_ns(ctx.trace, reduce.in_scope("telemetry/"))
    return 100.0 * sync / busy if sync > 0 else None
