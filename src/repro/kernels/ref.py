"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are validated against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose in
interpret mode). They are test oracles only: no code path falls back to
them, on any backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def pairwise_gram(xs: jnp.ndarray) -> jnp.ndarray:
    """Worker Gram matrix. xs: [W, d] -> [W, W] fp32."""
    x32 = xs.astype(jnp.float32)
    return x32 @ x32.T


def cwise_median(xs: jnp.ndarray) -> jnp.ndarray:
    """Coordinate-wise median over the worker axis. [W, d] -> [d] (fp32)."""
    return jnp.median(xs.astype(jnp.float32), axis=0)


def cwise_trimmed_mean(xs: jnp.ndarray, n_trim: int) -> jnp.ndarray:
    """Mean of the sorted [n_trim, W-n_trim) worker band. [W, d] -> [d] fp32."""
    s = jnp.sort(xs.astype(jnp.float32), axis=0)
    return jnp.mean(s[n_trim: xs.shape[0] - n_trim], axis=0)


def bucket_mix(mix: jnp.ndarray, xs: jnp.ndarray) -> jnp.ndarray:
    """Apply the mixing operator: [m, W] @ [W, d] -> [m, d] fp32."""
    return mix.astype(jnp.float32) @ xs.astype(jnp.float32)


def residual_norms(xs: jnp.ndarray, coeffs: jnp.ndarray) -> jnp.ndarray:
    """Per-worker residual sq-norms ``r_i = ||x_i - c^T X||^2``. -> [W] fp32."""
    x32 = xs.astype(jnp.float32)
    v = coeffs.astype(jnp.float32) @ x32
    return jnp.sum(jnp.square(x32 - v[None, :]), axis=1)


def cclip_combine(xs: jnp.ndarray, v: jnp.ndarray, lam: jnp.ndarray) -> jnp.ndarray:
    """One centered-clipping update: ``v + mean_i lam_i (x_i - v)``. -> [d] fp32."""
    x32 = xs.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    return v32 + jnp.mean(lam.astype(jnp.float32)[:, None] * (x32 - v32[None, :]), axis=0)


# ------------------------------------------------- composed aggregator refs
def cclip_aggregate(xs: jnp.ndarray, tau: float, n_iters: int = 3, eps: float = 1e-12):
    """Full CCLIP in vector space (oracle for ops.cclip_aggregate)."""
    x32 = xs.astype(jnp.float32)
    v = jnp.mean(x32, axis=0)
    for _ in range(n_iters):
        norms = jnp.sqrt(jnp.sum(jnp.square(x32 - v[None, :]), axis=1) + eps)
        lam = jnp.minimum(1.0, tau / norms)
        v = cclip_combine(x32, v, lam)
    return v


def rfa_aggregate(xs: jnp.ndarray, n_iters: int = 8, eps: float = 1e-6):
    """Smoothed Weiszfeld in vector space (oracle for ops.rfa_aggregate)."""
    x32 = xs.astype(jnp.float32)
    n = xs.shape[0]
    c = jnp.full((n,), 1.0 / n, jnp.float32)
    for _ in range(n_iters):
        r = jnp.sqrt(residual_norms(x32, c) + eps**2)
        w = 1.0 / r
        c = w / jnp.sum(w)
    return c @ x32


def attention(q, k, v, causal=True, window=0, q_offset=None):
    """Oracle for flash_attention. q: [B,Sq,H,dh]; k,v: [B,Skv,KV,dh]."""
    B, Sq, H, dh = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    off = Skv - Sq if q_offset is None else q_offset
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * dh ** -0.5
    qpos = off + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
