"""jaxpr lint: rules over the closed jaxpr of the hot-path functions.

The jaxpr is the layer where routing decisions are still visible as named
primitives (``pallas_call``, ``shard_map``, ``pure_callback``) before XLA
lowers them away — the right place to catch PR 7's failure mode, where
``use_kernels=True`` silently took the jnp route and nothing in the test
suite noticed. Rules:

  jaxpr-callback
      ``debug_callback`` / ``io_callback`` / ``pure_callback`` equation in
      the hot path — a host round-trip per step.

  jaxpr-f64
      An equation produces a float64/complex128 value (weak-type f32→f64
      promotion; only observable when x64 is enabled, but cheap to check
      everywhere).

  jaxpr-pallas-missing
      The function was built with ``use_kernels=True`` but its jaxpr
      contains NO ``pallas_call`` equation — the silent jnp fallback.
      Works on every backend, including CPU interpret mode, because the
      check runs before lowering erases the primitive.
"""

from __future__ import annotations

from typing import Any, Iterator, List

from repro.analysis.findings import ERROR, Finding

_CALLBACK_PRIMITIVES = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "callback"})
_PALLAS_PRIMITIVES = frozenset({"pallas_call"})


def _sub_jaxprs(params: dict) -> Iterator[Any]:
    """Every Jaxpr/ClosedJaxpr hiding in an equation's params (pjit
    call_jaxpr, shard_map jaxpr, scan/while bodies, cond branches, ...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def rec(v):
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for item in v:
                yield from rec(item)
        elif isinstance(v, dict):
            for item in v.values():
                yield from rec(item)

    for v in params.values():
        yield from rec(v)


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """All equations of a (closed) jaxpr, recursively through sub-jaxprs."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def primitive_counts(jaxpr: Any) -> dict:
    out: dict = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        out[name] = out.get(name, 0) + 1
    return out


def lint_jaxpr(jaxpr: Any, target: str,
               expect_pallas: bool = False) -> List[Finding]:
    """Run every jaxpr rule over one traced function."""
    import numpy as np

    findings: List[Finding] = []
    n_pallas = 0
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in _PALLAS_PRIMITIVES:
            n_pallas += 1
        if name in _CALLBACK_PRIMITIVES:
            findings.append(Finding(
                rule="jaxpr-callback", severity=ERROR, target=target,
                location=f"{name} eqn",
                message=(f"{name} in the hot path — a host round-trip "
                         f"per step (params: "
                         f"{sorted(eqn.params)[:4]})")))
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and dtype in (np.float64, np.complex128):
                findings.append(Finding(
                    rule="jaxpr-f64", severity=ERROR, target=target,
                    location=f"{name} eqn",
                    message=(f"{name} produces {dtype} — weak-type f32→f64 "
                             f"promotion in the hot path")))
                break  # one finding per eqn
    if expect_pallas and n_pallas == 0:
        findings.append(Finding(
            rule="jaxpr-pallas-missing", severity=ERROR, target=target,
            location="whole jaxpr",
            message=("use_kernels=True but the traced jaxpr has no "
                     "pallas_call equation — the kernel route silently "
                     "fell back to jnp")))
    return findings
