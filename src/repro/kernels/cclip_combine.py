"""Pallas TPU kernel: fused centered-clipping update.

One CCLIP iteration ``v' = v + (1/W) sum_i lam_i (x_i - v)`` with the clip
weights ``lam`` already known (from ``weiszfeld_norms``): a fused
scale-subtract-accumulate streaming over the parameter dimension. Together
with the norms kernel this makes one CCLIP iteration exactly TWO HBM passes
over the ``W x d`` gradients (norms pass + combine pass) and zero
materialized temporaries.

Padding rows carry lam = 0 and x = 0, so they contribute exactly 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _combine_kernel(lam_ref, v_ref, x_ref, out_ref, *, W: int):
    lam = lam_ref[...].astype(jnp.float32)      # [1, Wp]
    v = v_ref[...].astype(jnp.float32)          # [1, bd]
    x = x_ref[...].astype(jnp.float32)          # [Wp, bd]
    upd = jax.lax.dot_general(                  # [1, bd] = lam @ (x - v)
        lam, x - v, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    )
    out_ref[...] = v + upd / W


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def cclip_combine(xs: jnp.ndarray, v: jnp.ndarray, lam: jnp.ndarray, *,
                  block_d: int = 2048, interpret: bool | None = None):
    """xs: [W, d]; v: [d]; lam: [W] -> updated center [d] fp32."""
    from repro.kernels.ops import _interp  # ops imports this module

    interpret = _interp(interpret)
    W, d = xs.shape
    Wp = max(8, -(-W // 8) * 8)
    bd = min(block_d, max(128, -(-d // 128) * 128))
    bd = -(-bd // 128) * 128
    dp = -(-d // bd) * bd
    x = jnp.zeros((Wp, dp), xs.dtype).at[:W, :d].set(xs)
    vp = jnp.zeros((1, dp), jnp.float32).at[0, :d].set(v.astype(jnp.float32))
    lm = jnp.zeros((1, Wp), jnp.float32).at[0, :W].set(lam.astype(jnp.float32))

    out = pl.pallas_call(
        functools.partial(_combine_kernel, W=W),
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((1, Wp), lambda k: (0, 0)),
            pl.BlockSpec((1, bd), lambda k: (0, k)),
            pl.BlockSpec((Wp, bd), lambda k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, bd), lambda k: (0, k)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
        name="cclip_combine",
    )(lm, vp, x)
    return out[0, :d]
