"""Pallas TPU kernel: per-worker residual norms ``r_i = ||x_i - v||^2``.

The inner loop of smoothed Weiszfeld (RFA) and of CCLIP's Gram-free form.
The center ``v`` is given either

- in COEFFICIENT form (``coeffs``): ``v = c^T X`` for combination
  coefficients ``c`` over the worker rows. The candidate ``v`` is formed
  blockwise in VMEM (``c @ x_blk``) and subtracted immediately, so ``v``
  never round-trips to HBM. A fused (matvec + subtract + square +
  row-reduce) pass; or
- as an EXPLICIT row (``center``): an arbitrary ``[d]`` vector streamed
  block-aligned with ``xs``. This is what CCLIP's warm-started iterations
  need — callers no longer have to append ``v`` to the stack as a
  pseudo-row (which cost a full ``jnp.concatenate`` copy of the stack per
  iteration before this existed).

Padding: extra worker rows are zero, producing garbage residuals that the
wrapper slices off; extra d columns are zero in both x and v, contributing 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _resid_kernel(c_ref, x_ref, out_ref):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)          # [Wp, bd]
    c = c_ref[...].astype(jnp.float32)          # [1, Wp]
    v = jax.lax.dot_general(                    # [1, bd]
        c, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    )
    diff = x - v
    out_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True).T  # [1, Wp]


def _resid_center_kernel(v_ref, x_ref, out_ref):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)          # [Wp, bd]
    v = v_ref[...].astype(jnp.float32)          # [1, bd]
    diff = x - v
    out_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True).T  # [1, Wp]


def _pad_dims(W, d, block_d):
    Wp = max(8, -(-W // 8) * 8)
    bd = min(block_d, max(128, -(-d // 128) * 128))
    bd = -(-bd // 128) * 128
    dp = -(-d // bd) * bd
    return Wp, bd, dp


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def residual_norms(xs: jnp.ndarray, coeffs: jnp.ndarray | None = None, *,
                   center: jnp.ndarray | None = None, block_d: int = 2048,
                   interpret: bool | None = None):
    """xs: [W, d] -> residual sq norms [W] fp32 against the center given
    either as ``coeffs: [W]`` (``v = coeffs^T xs``) or as an explicit
    ``center: [d]`` row. Exactly one of the two must be provided."""
    from repro.kernels.ops import _interp  # ops imports this module

    interpret = _interp(interpret)
    if (coeffs is None) == (center is None):
        raise ValueError("provide exactly one of coeffs / center")
    W, d = xs.shape
    Wp, bd, dp = _pad_dims(W, d, block_d)
    x = jnp.zeros((Wp, dp), xs.dtype).at[:W, :d].set(xs)

    if coeffs is not None:
        first = jnp.zeros((1, Wp), jnp.float32).at[0, :W].set(
            coeffs.astype(jnp.float32))
        kernel = _resid_kernel
        first_spec = pl.BlockSpec((1, Wp), lambda k: (0, 0))
    else:
        first = jnp.zeros((1, dp), jnp.float32).at[0, :d].set(
            center.astype(jnp.float32))
        kernel = _resid_center_kernel
        first_spec = pl.BlockSpec((1, bd), lambda k: (0, k))

    out = pl.pallas_call(
        kernel,
        grid=(dp // bd,),
        in_specs=[
            first_spec,
            pl.BlockSpec((Wp, bd), lambda k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, Wp), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, Wp), jnp.float32),
        interpret=interpret,
        name="residual_norms",
    )(first, x)
    return out[0, :W]
