"""Predicates over the train step's name scopes, for the readers of the
model and train step layers (``reduce.busy_ns`` with these predicates).

The program names its phases with ``jax.named_scope`` (the tuple
``repro.telemetry.profiling.SCOPES``), and the scope reaches each op of the
trace through the compiled program's ``op_name``
(``reduce.scopes_from_hlo``). JAX writes its transformations around the
scopes they apply to, so a backward op reads
``.../step/grad/vmap(transpose(jvp(model/unembed)))/...``. A fusion carries
the ``op_name`` of its root.
"""

from __future__ import annotations

from bench import reduce


def forward(o: list) -> bool:
    return "step/grad" in o[5] and "transpose(" not in o[5]


def backward(o: list) -> bool:
    return "step/grad" in o[5] and "transpose(" in o[5]


def ssd_scan(o: list) -> bool:
    return "ssd/chunk" in o[5] or "ssd/state" in o[5]


def ms_per_unit(ctx, pred):
    """Device time (ms) per unit of the ops matching ``pred``, chip 0."""
    t_ns = reduce.busy_ns(ctx.trace, 0, pred)
    return t_ns / 1e6 / ctx.units if t_ns > 0 else None
