"""The whole sync call's share (%) of the chip's peak: the least time the
call's work needs at the bound that binds it (HBM for every rule here: the
W messages read once, the aggregate written once; ``cost.sync_call_cost``)
times the calls completed in the window, over the window."""

from bench import cost


def read(ctx):
    least_s, _ = cost.roofline_s(ctx.costs["flops_per_unit"],
                                 ctx.costs["bytes_per_unit"], ctx.peak)
    return 100.0 * least_s * ctx.units / ctx.window_s
