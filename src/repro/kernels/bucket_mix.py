"""Pallas TPU kernel: apply the paper's mixing operator ``y = M @ x``.

Bucketing/resampling (Algorithm 1) is a row-stochastic ``[m, W]`` matrix
applied to the stacked worker gradients. The matrix is tiny and replicated;
the gradient dimension streams through VMEM in 128-aligned blocks, so the
mix costs exactly one read + one write of HBM — it fuses the permute,
bucket-average and (optional) replication of Algorithm 1 into a single pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mix_kernel(m_ref, x_ref, out_ref):
    m = m_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = jax.lax.dot_general(
        m, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def bucket_mix(mix: jnp.ndarray, xs: jnp.ndarray, *, block_d: int = 2048,
               interpret: bool | None = None):
    """mix: [m, W] row-stochastic; xs: [W, d] -> mixed [m, d] fp32."""
    from repro.kernels.ops import _interp  # ops imports this module

    interpret = _interp(interpret)
    m, W = mix.shape
    W2, d = xs.shape
    assert W == W2, (mix.shape, xs.shape)
    mp = max(8, -(-m // 8) * 8)
    Wp = max(8, -(-W // 8) * 8)
    bd = min(block_d, max(128, -(-d // 128) * 128))
    bd = -(-bd // 128) * 128
    dp = -(-d // bd) * bd
    mx = jnp.zeros((mp, Wp), jnp.float32).at[:m, :W].set(mix.astype(jnp.float32))
    x = jnp.zeros((Wp, dp), xs.dtype).at[:W, :d].set(xs)

    out = pl.pallas_call(
        _mix_kernel,
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((mp, Wp), lambda k: (0, 0)),
            pl.BlockSpec((Wp, bd), lambda k: (0, k)),
        ],
        out_specs=pl.BlockSpec((mp, bd), lambda k: (0, k)),
        out_shape=jax.ShapeDtypeStruct((mp, dp), jnp.float32),
        interpret=interpret,
        name="bucket_mix",
    )(mx, x)
    return out[:m, :d]
