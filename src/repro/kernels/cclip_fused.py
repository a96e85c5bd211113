"""Pallas TPU kernel: fused centered-clipping iteration (combine + norms).

One CCLIP step with the clip weights ``lam`` already known does

    v' = v + (1/W) sum_i lam_i (x_i - v)          (combine)
    r_i' = ||x_i - v'||^2                          (norms for the NEXT lam)

Both are streamed in a SINGLE pass over the ``[W, d]`` stack: each ``bd``
block of ``v'`` is formed in VMEM (``lam @ (x_blk - v_blk)``), written out,
and immediately reused to accumulate the next iteration's residual norms —
so per CCLIP iteration the gradients leave HBM exactly once, instead of the
pre-fusion schedule of one norms kernel over a ``[W+1, d]`` pseudo-row stack
(built by a full `jnp.concatenate` copy) plus one combine kernel, i.e. one
HBM pass instead of two passes and a stack-sized copy.

Padding rows carry lam = 0 and x = 0, so they contribute exactly 0 to the
update; their residuals are garbage and are sliced off by the wrapper.
Padded d columns are zero in x and v, stay zero in v', and contribute 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fused_kernel(lam_ref, v_ref, x_ref, vout_ref, r2_ref, *, W: int):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        r2_ref[...] = jnp.zeros_like(r2_ref)

    lam = lam_ref[...].astype(jnp.float32)      # [1, Wp]
    v = v_ref[...].astype(jnp.float32)          # [1, bd]
    x = x_ref[...].astype(jnp.float32)          # [Wp, bd]
    upd = jax.lax.dot_general(                  # [1, bd] = lam @ (x - v)
        lam, x - v, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    )
    v_new = v + upd / W
    vout_ref[...] = v_new
    diff = x - v_new
    r2_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True).T  # [1, Wp]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def cclip_fused_iter(xs: jnp.ndarray, v: jnp.ndarray, lam: jnp.ndarray, *,
                     block_d: int = 2048, interpret: bool | None = None):
    """xs: [W, d]; v: [d]; lam: [W] -> (v' [d] fp32, ||x_i - v'||^2 [W] fp32)."""
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

    v_new, r2 = pl.pallas_call(
        functools.partial(_fused_kernel, W=W),
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((1, Wp), lambda k: (0, 0)),
            pl.BlockSpec((1, bd), lambda k: (0, k)),
            pl.BlockSpec((Wp, bd), lambda k: (0, k)),
        ],
        out_specs=[
            pl.BlockSpec((1, bd), lambda k: (0, k)),
            pl.BlockSpec((1, Wp), lambda k: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, Wp), jnp.float32),
        ],
        interpret=interpret,
    )(lm, vp, x)
    return v_new[0, :d], r2[0, :W]
