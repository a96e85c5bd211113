"""Pallas TPU kernel: causal flash attention with GQA (+ sliding window).

The TPU-target fast path for ``repro.models.attention`` (the pure-JAX
``blockwise`` impl is the dry-run/CPU path; both share the same online-
softmax recurrence and are validated against ``ref.attention``).

Grid layout: (batch, q_heads, q_blocks) with the KV loop INSIDE the kernel
(fori_loop over KV blocks) so the running (m, l, acc) state stays in
registers/VMEM — the canonical TPU flash scheme. BlockSpecs stage one
[bq, dh] query tile and the full [Skv, dh] K/V for the mapped kv-head in
VMEM; for the assigned shapes (dh 64-256, Skv <= 32k bf16) that is <= 16 MB
and within v5e VMEM budget when bkv-tiled by the inner loop.

Causal + sliding-window masking is positional (absolute positions passed
per block), so the same kernel serves train (Sq == Skv) and chunked prefill
(Sq < Skv with a prefix offset).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, bkv, causal, window,
                  q_offset):
    # q_ref: [bq, dh]; k_ref/v_ref: [Skv, dh]; o_ref: [bq, dh]
    qi = pl.program_id(2)
    bq, dh = q_ref.shape
    Skv = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * scale
    qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    nkv = Skv // bkv

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[pl.dslice(ki * bkv, bkv), :].astype(jnp.float32)
        v = v_ref[pl.dslice(ki * bkv, bkv), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bkv]
        kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nkv, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_kv", "q_offset",
                     "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # [B, Sq, H, dh]
    k: jnp.ndarray,  # [B, Skv, KV, dh]
    v: jnp.ndarray,  # [B, Skv, KV, dh]
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    q_offset: int = -1,  # -1 => Skv - Sq (decode-style suffix alignment)
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns [B, Sq, H, dh]. GQA: each query head h reads kv head
    h // (H // KV). Sq must be divisible by block_q and Skv by block_kv
    (callers pick divisor blocks; see models.attention._divisor_block)."""
    from repro.kernels.ops import _interp  # ops imports this module

    interpret = _interp(interpret)
    B, Sq, H, dh = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, block_q, Skv, block_kv)
    rep = H // KV
    off = Skv - Sq if q_offset == -1 else q_offset
    scale = dh ** -0.5

    # [B, S, H, dh] -> [B, H, S, dh] so the head becomes a grid dim
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _flash_kernel, scale=scale, bkv=block_kv, causal=causal,
        window=window, q_offset=off,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, Sq // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, Skv, dh),
                         lambda b, h, i, _rep=rep: (b, h // _rep, 0, 0)),
            pl.BlockSpec((None, None, Skv, dh),
                         lambda b, h, i, _rep=rep: (b, h // _rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, dh),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dh), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)
