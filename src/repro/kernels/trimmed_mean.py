"""Pallas TPU kernel: coordinate-wise trimmed mean over the worker axis.

Same engine as cwise_median: a pruned Batcher odd-even merge selection
network (repro/kernels/selection_network.py) materializes the sorted
``[b, W-b)`` band per coordinate with static vectorized min/max
compare-exchanges, then averages the band in one pass. ``n_trim == 0``
skips the network entirely (a mean is order-free). Fully unrolled at trace
time; padding rows exist only for the sublane-aligned BlockSpec and are
never read (the program references no slot >= W).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.selection_network import (
    apply_program,
    selection_program,
    trim_ranks,
)


def _tm_kernel(x_ref, out_ref, *, W: int, n_trim: int):
    x = x_ref[...].astype(jnp.float32)  # [Wp, bd]
    rows = [x[i] for i in range(W)]
    if n_trim > 0:
        ranks = trim_ranks(W, n_trim)
        sorted_rows = apply_program(rows, selection_program(W, ranks))
        band = [sorted_rows[r] for r in ranks]
    else:
        band = rows
    acc = band[0]
    for row in band[1:]:
        acc = acc + row
    out_ref[...] = (acc / float(len(band)))[None, :]


@functools.partial(jax.jit, static_argnames=("n_trim", "block_d", "interpret"))
def cwise_trimmed_mean(xs: jnp.ndarray, n_trim: int, *, block_d: int = 4096,
                       interpret: bool | None = None):
    """xs: [W, d] -> mean of the sorted [n_trim, W-n_trim) worker band, [d]
    fp32. ``n_trim`` must satisfy ``0 <= n_trim <= (W - 1) // 2`` (callers
    clamp; asserted here because the band must be non-empty)."""
    from repro.kernels.ops import _interp  # ops imports this module

    interpret = _interp(interpret)
    W, d = xs.shape
    if not 0 <= n_trim <= (W - 1) // 2:
        raise ValueError(f"n_trim={n_trim} out of range for W={W}")
    Wp = max(8, -(-W // 8) * 8)
    if interpret:
        # one wide block per dispatch batch — see cwise_median.py; VMEM
        # tiling only binds on a real TPU (interpret=False).
        block_d = max(block_d, min(-(-d // 128) * 128, 1 << 20))
    bd = min(block_d, max(128, -(-d // 128) * 128))
    bd = -(-bd // 128) * 128
    dp = -(-d // bd) * bd
    x = jnp.full((Wp, dp), jnp.inf, jnp.float32).at[:W, :d].set(
        xs.astype(jnp.float32)
    )

    out = pl.pallas_call(
        functools.partial(_tm_kernel, W=W, n_trim=n_trim),
        grid=(dp // bd,),
        in_specs=[pl.BlockSpec((Wp, bd), lambda k: (0, k))],
        out_specs=pl.BlockSpec((1, bd), lambda k: (0, k)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
        name="cwise_trimmed_mean",
    )(x)
    return out[0, :d]
