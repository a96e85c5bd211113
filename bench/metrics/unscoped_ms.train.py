"""Device time (ms) per train step in which the timed step program ran only
ops under no ``step/`` scope: what the compiler inserted without metadata
(XLA's relayout loops and layout copies). An unscoped op nested inside a
scoped one (a copy in the layer scan's body) counts once, with the scoped
op. It guards the attribution of the other train-step metrics: with
``step/sync`` they sum to the program's busy time. None for a program that
names no ``step/`` scope at all."""

from bench import reduce


def read(ctx):
    if not any("step/" in o[5] for o in ctx.trace.ops):
        return None
    program = lambda o: o[4] in ctx.trace.programs  # noqa: E731
    t_ns = (reduce.busy_ns(ctx.trace, 0, program)
            - reduce.busy_ns(ctx.trace, 0, lambda o: program(o) and "step/" in o[5]))
    return t_ns / 1e6 / ctx.units if t_ns > 0 else None
