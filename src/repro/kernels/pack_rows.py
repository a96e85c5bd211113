"""Pallas TPU kernel: write one stacked leaf into its segment of the packed
sync buffer, in the layout the sync kernels read.

A leaf ``[W, R, C]`` (R rows of minor dim C per worker) goes to columns
``[off, off + seg)`` of the ``[Wp, n]`` fp32 buffer as its lane-aligned
``[R, C']`` view (repro/distributed/packing.py, LAYOUT): buffer row w holds
worker w's R rows one after another, each zero-padded from C to C' lanes,
then zeros up to ``seg``; rows W..Wp are zero.

That move takes a worker's rows out of the sublanes of its own ``(8, 128)``
tiles and lays them along the lanes of one buffer row: a transposition at
sublane granularity. XLA does it in three HBM passes (a pad to the 8-row
multiple, a transposing copy, a copy into the buffer). This kernel does it
in one. Each grid step reads ``[8 workers, tr rows, C]``, gathers row r of
the 8 workers into one ``[8, C]`` value for each r, and sends the
``[8, tr * C']`` result to the buffer by DMA, double-buffered against the
next step's gathers. The
buffer is aliased in and out, so a chain of calls, one per leaf, fills it
with no zero-fill pass; a call without a buffer allocates it.
"""

from __future__ import annotations

import functools
from typing import Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_STEP_BYTES = 1 << 20   # target size of one step's [8, tr * C'] block
# The largest such block (at 8 rows) a leaf may need: two input and two
# output blocks then fit the default scoped VMEM. Asking for more VMEM
# (``vmem_limit_bytes``) is no option: on a v5e it made XLA place the train
# step's other ops worse, 2.8 ms of model time per step.
_MAX_BYTES = 2 << 20


def _lanes(c: int) -> int:
    return -(-c // 128) * 128


def _rows_per_step(R: int, C: int) -> int:
    """Leaf rows per grid step: the whole leaf below 8 rows, else a multiple
    of 8 (the input block's sublane tiling) near ``_STEP_BYTES``."""
    if R < 8:
        return R
    tr = max(8, _STEP_BYTES // (8 * _lanes(C) * 4) // 8 * 8)
    return min(tr, R // 8 * 8)


def supports(R: int, C: int) -> bool:
    """Whether ``pack_rows`` writes a leaf of R rows of minor dim C: rows
    that are whole lane tiles wide (C >= 128), more than one of them, and a
    step that fits VMEM."""
    return (C >= 128 and R > 1
            and 8 * _rows_per_step(R, C) * _lanes(C) * 4 <= _MAX_BYTES)


def _pack_kernel(x_ref, *refs, W, R, C, tr, off, seg, n_steps):
    *_, out_ref, scr, sem = refs    # the aliased input buffer is out_ref
    g, i = pl.program_id(0), pl.program_id(1)
    gw = x_ref.shape[0]
    Cp = _lanes(C)
    width = tr * Cp
    slot = i % 2

    def copy(s, step, w):
        return pltpu.make_async_copy(
            scr.at[s, :, pl.ds(0, w)],
            out_ref.at[pl.ds(g * 8, 8), pl.ds(off + step * width, w)],
            sem.at[s])

    @pl.when((g == 0) & (i == 0))
    def _zero():  # lanes C..C' and rows gw..8 are never written again
        scr[...] = jnp.zeros(scr.shape, scr.dtype)

    @pl.when(i >= 2)
    def _free_slot():
        copy(slot, i - 2, width).wait()

    live = jax.lax.broadcasted_iota(jnp.int32, (gw, C), 0) < W - g * 8

    def put(r, row):  # row r of the block's gw workers, [gw, C]
        keep = live & (i * tr + r < R)
        col = pl.multiple_of(r * Cp, 128)
        scr[slot, :gw, pl.ds(col, C)] = jnp.where(
            keep, row.astype(jnp.float32), 0.0)

    if tr < 8:
        for r in range(tr):
            put(r, x_ref[:, r, :])
    else:  # one aligned 8-row load per loop step keeps the body small
        def rows8(j, carry):
            rows = x_ref[:, pl.ds(pl.multiple_of(j * 8, 8), 8), :]
            for k in range(8):
                put(j * 8 + k, rows[:, k, :])
            return carry

        jax.lax.fori_loop(0, tr // 8, rows8, 0)

    @pl.when(i < n_steps - 1)
    def _send():
        copy(slot, i, width).start()

    @pl.when(i == n_steps - 1)
    def _send_last():
        last = copy(slot, i, seg - (n_steps - 1) * width)
        last.start()
        last.wait()
        if n_steps > 1:
            copy(1 - slot, i - 1, width).wait()


def pack_rows(x: jnp.ndarray, buf: Union[jnp.ndarray, Tuple[int, int]], *,
              off: int, seg: int, interpret: bool | None = None) -> jnp.ndarray:
    """x: [W, R, C] leaf; buf: the ``[Wp, n]`` fp32 buffer, or its shape for
    a new one -> the buffer with columns ``[off, off + seg)`` holding x's
    lane-aligned view (module docstring). ``supports(R, C)`` must hold,
    ``off`` and ``seg`` be multiples of 128, and ``seg >= R * C'``."""
    from repro.kernels.ops import _interp  # ops imports this module

    W, R, C = x.shape
    shape = tuple(buf) if isinstance(buf, tuple) else buf.shape
    Wp = shape[0]
    assert supports(R, C) and Wp % 8 == 0 and Wp >= W, (x.shape, shape)
    assert off % 128 == 0 and seg % 128 == 0 and seg >= R * _lanes(C), (off, seg)
    tr = _rows_per_step(R, C)
    gw = min(8, W)
    n_steps = -(-seg // (tr * _lanes(C)))
    last_block = -(-R // tr) - 1
    fresh = isinstance(buf, tuple)
    return pl.pallas_call(
        functools.partial(_pack_kernel, W=W, R=R, C=C, tr=tr, off=off,
                          seg=seg, n_steps=n_steps),
        grid=(Wp // 8, n_steps),
        in_specs=[pl.BlockSpec((gw, tr, C),
                               lambda g, i: (g, jnp.minimum(i, last_block), 0))]
        + ([] if fresh else [pl.BlockSpec(memory_space=pl.ANY)]),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, 8, tr * _lanes(C)), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        input_output_aliases={} if fresh else {1: 0},
        compiler_params=pltpu.CompilerParams(  # steps share the scratch
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interp(interpret),
        name="pack_rows",
    )(x, *([] if fresh else [buf]))
