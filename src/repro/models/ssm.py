"""Mamba-2 (SSD — state-space duality) block, chunked-scan implementation.

Follows the minimal SSD algorithm of Dao & Gu (arXiv:2405.21060): the
sequence is split into chunks of length Q; within a chunk the recurrence is
computed in its dual quadratic (attention-like) form on the MXU, and chunk
boundary states are propagated with a sequential ``lax.scan`` (O(S/Q) steps).
This is the TPU-native adaptation: the quadratic intra-chunk part is a
dense matmul workload, and the inter-chunk scan is tiny ([B, H, P, N]).

Decode: O(1) recurrent state update — the reason the ``long_500k`` shape is
trivially supported for SSM archs.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rmsnorm


# ------------------------------------------------------------------ params
def init_ssm(key, cfg) -> dict:
    d, din = cfg.d_model, cfg.d_inner
    n, h = cfg.ssm_state, cfg.ssm_heads
    g = 1  # ssm groups
    kconv = cfg.conv_kernel
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    d_in_proj = 2 * din + 2 * g * n + h  # z, x, B, C, dt
    conv_ch = din + 2 * g * n
    return {
        "in_proj": dense_init(ks[0], d, d_in_proj, dtype),
        "conv_w": (jax.random.normal(ks[1], (conv_ch, kconv), jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm_scale": jnp.ones((din,), dtype),
        "out_proj": dense_init(ks[2], din, d, dtype),
    }


# ---------------------------------------------------------------- helpers
def _segsum_exp(da: jnp.ndarray) -> jnp.ndarray:
    """da: [..., L] -> lower-triangular decay matrix exp(sum_{j<k<=i} da_k).

    L[i, j] = exp(cumsum_i - cumsum_j) for j <= i, else 0.
    """
    cs = jnp.cumsum(da, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    L = da.shape[-1]
    tri = jnp.tril(jnp.ones((L, L), bool))
    # Mask before the exp: above the diagonal diff > 0 overflows once a
    # chunk's decay passes ~88, and where(tri, exp(diff), 0) then
    # back-propagates 0 * inf = NaN into dt and A.
    return jnp.exp(jnp.where(tri, diff, -jnp.inf))


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv as K shifted multiply-adds. x: [B, S, C]; w: [C, K].

    Deliberately NOT lax.conv_general_dilated(feature_group_count=C): XLA
    lowers that conv's filter gradient to a full cross-channel correlation
    (observed: f32[K, B*C, B*C] — 2.8e17 FLOPs for jamba train_4k, 200x the
    whole model; see EXPERIMENTS.md §Perf iteration 1). K is 4: unrolled
    shift-and-add is exact, differentiates cleanly, and is a pure VPU
    (elementwise) workload on TPU — strictly better than a grouped conv.
    """
    K = w.shape[1]
    x32 = x.astype(jnp.float32)
    xp = jnp.pad(x32, ((0, 0), (K - 1, 0), (0, 0)))
    S = x.shape[1]
    out = b.astype(jnp.float32)[None, None, :] + sum(
        xp[:, k : k + S, :] * w[:, k].astype(jnp.float32)[None, None, :]
        for k in range(K)
    )
    return out.astype(x.dtype)


# ------------------------------------------------------------------- train
def ssd_scan(x, dt, A, B_, C_, chunk: int):
    """Chunked SSD. x: [B,S,H,P]; dt: [B,S,H]; A: [H] (negative);
    B_, C_: [B,S,G,N] (G=1). Returns y: [B,S,H,P] and final state [B,H,P,N]."""
    Bsz, S, H, P = x.shape
    N = B_.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    # broadcast groups (G=1) over heads
    Bh = jnp.broadcast_to(B_[:, :, 0:1], (Bsz, S, 1, N))[:, :, 0]  # [B,S,N]
    Ch = jnp.broadcast_to(C_[:, :, 0:1], (Bsz, S, 1, N))[:, :, 0]

    xc = x.reshape(Bsz, nc, Q, H, P).astype(jnp.float32)
    dtc = dt.reshape(Bsz, nc, Q, H).astype(jnp.float32)
    Bc = Bh.reshape(Bsz, nc, Q, N).astype(jnp.float32)
    Cc = Ch.reshape(Bsz, nc, Q, N).astype(jnp.float32)

    da = dtc * A[None, None, None, :]  # [B,c,Q,H]
    da_t = jnp.moveaxis(da, -1, -2)  # [B,c,H,Q]
    cs = jnp.cumsum(da_t, axis=-1)  # [B,c,H,Q]
    xdt = xc * dtc[..., None]  # input scaled by dt

    # intra-chunk (quadratic/dual form)
    Lm = _segsum_exp(da_t)  # [B,c,H,Q,Q]
    scores = jnp.einsum("bcln,bcsn->bcls", Cc, Bc)  # [B,c,Q,Q]
    y_diag = jnp.einsum("bcls,bchls,bcshp->bclhp", scores, Lm, xdt)

    # chunk-boundary states
    decay_to_end = jnp.exp(cs[..., -1:] - cs)  # [B,c,H,Q]
    states = jnp.einsum("bcsn,bchs,bcshp->bchpn", Bc, decay_to_end, xdt)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(cs[..., -1])  # [B,c,H]

    def step(h_prev, inp):
        st, dec = inp  # [B,H,P,N], [B,H]
        h_new = h_prev * dec[..., None, None] + st
        return h_new, h_prev

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    h_last, h_prevs = jax.lax.scan(
        step, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # [B,c,H,P,N], state entering chunk c

    # contribution of carried-in state
    decay_in = jnp.exp(cs)  # [B,c,H,Q]
    y_off = jnp.einsum("bcln,bchpn,bchl->bclhp", Cc, h_prevs, decay_in)

    y = (y_diag + y_off).reshape(Bsz, S, H, P)
    return y, h_last


def ssm_layer(p, hidden, cfg) -> jnp.ndarray:
    """Full Mamba-2 block (train). hidden: [B, S, D]."""
    B, S, D = hidden.shape
    din, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    P = cfg.ssm_head_dim

    zxbcdt = hidden @ p["in_proj"]
    z, xbc, dt_raw = jnp.split(zxbcdt, [din, din + din + 2 * n], axis=-1)
    xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"])
    xbc = jax.nn.silu(xbc)
    x, B_, C_ = jnp.split(xbc, [din, din + n], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # [B,S,H]
    A = -jnp.exp(p["A_log"])  # [H]
    xh = x.reshape(B, S, h, P)
    y, _ = ssd_scan(xh, dt, A, B_[:, :, None, :], C_[:, :, None, :], cfg.ssm_chunk)
    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, S, din).astype(hidden.dtype)

    # gated RMSNorm + out projection
    gated = y * jax.nn.silu(z)
    gated = rmsnorm({"scale": p["norm_scale"]}, gated, cfg.norm_eps)
    return gated @ p["out_proj"]


# ------------------------------------------------------------------ decode
def init_ssm_cache(batch: int, cfg, dtype) -> dict:
    din, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_ch = din + 2 * n
    return {
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, conv_ch), dtype),
        "ssm": jnp.zeros((batch, h, cfg.ssm_head_dim, n), jnp.float32),
    }


def decode_ssm(p, hidden, cache, cfg) -> Tuple[jnp.ndarray, dict]:
    """One-token recurrent step. hidden: [B, 1, D]."""
    B = hidden.shape[0]
    din, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    P = cfg.ssm_head_dim

    zxbcdt = hidden[:, 0] @ p["in_proj"]  # [B, ...]
    z, xbc, dt_raw = jnp.split(zxbcdt, [din, din + din + 2 * n], axis=-1)

    # conv ring: state holds the previous K-1 inputs
    conv_in = jnp.concatenate([cache["conv"], xbc[:, None, :]], axis=1)  # [B,K,C]
    conv_out = jnp.einsum("bkc,ck->bc", conv_in.astype(jnp.float32), p["conv_w"].astype(jnp.float32))
    xbc_t = jax.nn.silu(conv_out + p["conv_b"].astype(jnp.float32)).astype(hidden.dtype)
    new_conv = conv_in[:, 1:]

    x, B_, C_ = jnp.split(xbc_t, [din, din + n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # [B,H]
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A[None, :])  # [B,H]

    xh = x.reshape(B, h, P).astype(jnp.float32)
    # h' = dA h + dt * x (outer) B ; y = h' . C + D x
    upd = jnp.einsum("bh,bhp,bn->bhpn", dt, xh, B_.astype(jnp.float32))
    new_state = cache["ssm"] * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, C_.astype(jnp.float32))
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(B, din).astype(hidden.dtype)

    gated = y * jax.nn.silu(z)
    gated = rmsnorm({"scale": p["norm_scale"]}, gated, cfg.norm_eps)
    out = (gated @ p["out_proj"])[:, None, :]
    return out, {"conv": new_conv, "ssm": new_state}
