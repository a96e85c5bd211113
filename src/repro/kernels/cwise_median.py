"""Pallas TPU kernel: coordinate-wise median over the worker axis.

CM aggregates n <= 64 worker vectors per coordinate. On GPU this is a
per-thread selection; the TPU-native adaptation (DESIGN.md §3) keeps the
worker axis resident in sublanes and runs a **pruned Batcher odd-even merge
selection network** (repro/kernels/selection_network.py) — a static
compare-exchange program that materializes only the 1-2 middle order
statistics, vectorized min/max over [1, bd] rows, a pure VPU workload with
no data-dependent control flow. The program is built from static (W, ranks)
and fully unrolled at trace time, so Mosaic sees only static slices; it
replaces the old O(W^2) odd-even transposition sort (W=25: 113 comparators
vs 312).

Padding rows exist only for the sublane-aligned BlockSpec; the selection
program never references slots >= W (sentinel elimination), so their +inf
fill is never read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.selection_network import (
    apply_program,
    median_ranks,
    selection_program,
)


def _median_kernel(x_ref, out_ref, *, W: int):
    x = x_ref[...].astype(jnp.float32)  # [Wp, bd]
    ranks = median_ranks(W)
    rows = apply_program([x[i] for i in range(W)],
                         selection_program(W, ranks))
    if len(ranks) == 1:
        med = rows[ranks[0]]
    else:
        med = 0.5 * (rows[ranks[0]] + rows[ranks[1]])
    out_ref[...] = med[None, :]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def cwise_median(xs: jnp.ndarray, *, block_d: int = 4096,
                 interpret: bool | None = None):
    """xs: [W, d] -> median over workers [d] fp32."""
    from repro.kernels.ops import _interp  # ops imports this module

    interpret = _interp(interpret)
    W, d = xs.shape
    Wp = max(8, -(-W // 8) * 8)
    if interpret:
        # interpret mode pays one traced-op dispatch per comparator per grid
        # step, so fewer/wider blocks dominate; VMEM tiling only binds on a
        # real TPU (interpret=False). Cap the block to bound the buffer.
        block_d = max(block_d, min(-(-d // 128) * 128, 1 << 20))
    bd = min(block_d, max(128, -(-d // 128) * 128))
    bd = -(-bd // 128) * 128
    dp = -(-d // bd) * bd
    x = jnp.full((Wp, dp), jnp.inf, jnp.float32).at[:W, :d].set(
        xs.astype(jnp.float32)
    )

    out = pl.pallas_call(
        functools.partial(_median_kernel, W=W),
        grid=(dp // bd,),
        in_specs=[pl.BlockSpec((Wp, bd), lambda k: (0, k))],
        out_specs=pl.BlockSpec((1, bd), lambda k: (0, k)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
    )(x)
    return out[0, :d]
