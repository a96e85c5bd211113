"""Phase markers and one-call profiler trace capture.

``phase("pack")`` wraps a region in ``jax.named_scope("telemetry/pack")``:
every operation traced inside it carries that scope in its ``op_name``
metadata, through ``vmap``, ``jvp`` and ``transpose`` (backward operations
show ``transpose(`` in their ``op_name``), into the compiled HLO. Fusions
take the ``op_name`` of their root. A profiler's device trace names each
operation by its HLO instruction, so the scope of its time is read from the
compiled program's text (``bench/reduce.py``). A name scope touches only
metadata, so the collective budgets checked by ``repro.analysis`` are
unaffected, which is why the markers are always on, even with
``telemetry=False``. The marker is no host span: every caller runs under
``jit``, where the body's Python runs once, at trace time.

``SCOPES`` lists every scope the program emits, by layer: the robust sync's
phases (``telemetry/``), the train step's (``step/``), the model's
(``model/``) and the Mamba-2 SSD layer's parts (``ssd/``). Only the sync's
scopes contain ``telemetry/``.

``trace_capture`` is the one-call helper: run any callable under
``jax.profiler.start_trace`` / ``stop_trace`` with the result blocked on, so
the captured timeline actually contains the compute. View the output with
TensorBoard or Perfetto (``docs/observability.md``).
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable

import jax

SYNC_PHASES = ("pack", "mix", "gram", "coeff", "kernel", "combine", "unpack")

SCOPES = (
    *(f"telemetry/{p}" for p in SYNC_PHASES),
    "step/grad", "step/momentum", "step/sync", "step/optimizer",
    "model/embed", "model/unembed",
    "ssd/in_proj", "ssd/conv", "ssd/chunk", "ssd/state", "ssd/out_proj",
)


@contextlib.contextmanager
def phase(name: str, layer: str = "telemetry"):
    """Name the operations traced inside as ``<layer>/<name>`` (a
    ``jax.named_scope``; the scopes the program uses are ``SCOPES``)."""
    with jax.named_scope(f"{layer}/{name}"):
        yield


def trace_capture(logdir: str, fn: Callable[..., Any], *args: Any,
                  **kwargs: Any) -> Any:
    """Run ``fn(*args, **kwargs)`` under a jax profiler trace.

    Blocks on the result before stopping the trace so asynchronously
    dispatched device work is inside the capture window. Returns ``fn``'s
    result; the trace lands under ``logdir`` (open with TensorBoard's
    profile plugin or Perfetto)."""
    jax.profiler.start_trace(logdir)
    try:
        out = fn(*args, **kwargs)
        out = jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    return out
