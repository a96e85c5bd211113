"""Device time (ms) per train step of the unembed, forward and backward:
ops under ``model/unembed`` (the tied 50280-row logits, log-softmax and
the cross-entropy)."""

from bench import reduce


def read(ctx):
    return reduce.scope_ms_per_unit(ctx, "model/unembed")
