"""Pallas TPU kernel: worker Gram matrix ``G[i,j] = <x_i, x_j>``.

The stats phase of Krum / RFA / CCLIP is a rank-``d``
reduction of outer products — a natural MXU workload. The parameter
dimension is tiled into VMEM-resident ``[W, bd]`` blocks (``bd`` a multiple
of 128 so the contraction dim is MXU-aligned); the tiny ``[W, W]`` fp32
accumulator lives in the output block across the whole grid (revisited every
step, standard Pallas accumulation pattern).

HBM traffic: ``W*d`` input bytes read exactly once — the kernel is
memory-bound (arithmetic intensity W/2 FLOPs/byte), so the roofline target
is HBM bandwidth, which one-pass streaming achieves.

Chained accumulation (``acc``): the kernel can seed its accumulator from a
caller-supplied ``[W, W]`` matrix instead of zeros. Together with
``full_blocks=True`` (force every block to exactly ``block_d`` columns)
this makes a CHAIN of per-leaf calls perform the *identical* sequence of
block dots and fp32 adds as ONE call on the packed flat buffer whose leaf
segments are padded to ``block_d`` multiples — the bit-exactness bridge
between the per-leaf oracle and the packed engine
(repro/distributed/packing.py, asserted in tests/test_packing.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gram_kernel(acc_ref, x_ref, out_ref):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = acc_ref[...].astype(jnp.float32)

    x = x_ref[...].astype(jnp.float32)
    out_ref[...] += jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret", "full_blocks"))
def pairwise_gram(xs: jnp.ndarray, acc: jnp.ndarray | None = None, *,
                  block_d: int = 2048, interpret: bool | None = None,
                  full_blocks: bool = False):
    """xs: [W, d] (any float dtype) -> Gram [W, W] fp32 (``acc +`` if given).

    Pads W to a multiple of 8 (sublane) and d to a multiple of the block
    (lane=128-aligned); zero padding contributes 0 to every inner product.
    An ``xs`` already of the padded shape (the packed sync's buffer) is
    read as it is, with no copy. ``full_blocks`` forces the block width to
    exactly ``block_d`` (padding d up to a ``block_d`` multiple) so block
    shapes are independent of ``d``.
    """
    from repro.kernels.ops import _interp  # ops imports this module

    interpret = _interp(interpret)
    W, d = xs.shape
    Wp = max(8, -(-W // 8) * 8)
    if full_blocks:
        bd = -(-block_d // 128) * 128
    else:
        bd = min(block_d, max(128, -(-d // 128) * 128))
        bd = -(-bd // 128) * 128
    dp = max(bd, -(-d // bd) * bd)
    x = xs if (W, d) == (Wp, dp) else (
        jnp.zeros((Wp, dp), xs.dtype).at[:W, :d].set(xs))
    a = jnp.zeros((Wp, Wp), jnp.float32)
    if acc is not None:
        a = a.at[:W, :W].set(acc.astype(jnp.float32))

    out = pl.pallas_call(
        _gram_kernel,
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((Wp, Wp), lambda k: (0, 0)),
            pl.BlockSpec((Wp, bd), lambda k: (0, k)),
        ],
        out_specs=pl.BlockSpec((Wp, Wp), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Wp, Wp), jnp.float32),
        interpret=interpret,
        name="pairwise_gram",
    )(a, x)
    return out[:W, :W]
