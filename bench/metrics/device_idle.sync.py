"""Share (%) of the traced window in which no operation ran on the device:
1 - union of the device op intervals / window, averaged over the chips."""

from bench import reduce


def read(ctx):
    return 100.0 * reduce.idle_share(ctx.trace)
