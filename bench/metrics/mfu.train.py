"""Model FLOP/s utilisation (%) of the train step: the forward and backward
operations the model needs per step (``cost.train_step_flops``, no
recompute) times the steps completed in the window, over the window, the
chips and each chip's bf16 peak."""


def read(ctx):
    flops = ctx.costs["flops_per_unit"] * ctx.units
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peak["bf16_flops"])
