"""Plain Mamba-2 language model and its training steps, for the train cells.

Written from the published description (Dao & Gu, arXiv:2405.21060): each
layer is a pre-norm residual block around the Mamba-2 mixer: an input
projection to (z, x, B, C, dt), a depthwise causal convolution with SiLU
over (x, B, C), the selective state-space recurrence

    h_t = exp(dt_t * A) h_{t-1} + dt_t * x_t B_t^T,   y_t = h_t C_t + D x_t

written here in its quadratic "dual" form over the whole sequence (no
chunking, no scan), a gated RMSNorm ``rmsnorm(y * silu(z))`` and an output
projection; a final RMSNorm and the tied embedding give the logits.

Everything is float32, every contraction goes through one ``mm`` so that a
control can run the same arithmetic in a lower precision, and each layer is
rematerialised so that the backward pass fits beside the program's freed
state. Nothing here imports the program. The weights are the benchmark's
own (``init_params``), drawn from the seed in the layout the program takes.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from bench import cost
from bench.reference.robust import bucket_matrix, mix_key, rfa_weights

F32 = jnp.float32


def init_params(cfg: dict, key) -> dict:
    """Seeded weights in their served dtypes: matrices, norms and the
    embedding in the configuration's dtype; A_log, D and dt_bias in float32.
    Initialisation follows mamba_ssm: A in U(1, 16), dt in
    logU(1e-3, 1e-1), PyTorch's default for the depthwise convolution."""
    m = cost.mamba2_dims(cfg)
    d, din, n, h, k, L, V = (m["d"], m["din"], m["n"], m["h"], m["k"],
                             m["layers"], m["vocab"])
    dt = jnp.dtype(cfg["dtype"])
    conv_ch = din + 2 * n
    ks = jax.random.split(key, 8)

    def normal(kk, shape, scale):
        return (jax.random.normal(kk, shape, F32) * scale).astype(dt)

    def uniform(kk, shape, lo, hi):
        return jax.random.uniform(kk, shape, F32, lo, hi)

    dt0 = jnp.exp(uniform(ks[5], (L, h), math.log(1e-3), math.log(1e-1)))
    dt0 = jnp.maximum(dt0, 1e-4)
    bound = 1.0 / math.sqrt(k)
    mixer = {
        "in_proj": normal(ks[1], (L, d, 2 * din + 2 * n + h), d ** -0.5),
        "conv_w": uniform(ks[2], (L, conv_ch, k), -bound, bound).astype(dt),
        "conv_b": uniform(ks[3], (L, conv_ch), -bound, bound).astype(dt),
        "A_log": jnp.log(uniform(ks[4], (L, h), 1.0, 16.0)),
        "D": jnp.ones((L, h), F32),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
        "norm_scale": jnp.ones((L, din), dt),
        "out_proj": normal(ks[6], (L, din, d), din ** -0.5),
    }
    return {
        "embed": normal(ks[0], (V, d), 0.02),
        "blocks": {"0": {"norm1": {"scale": jnp.ones((L, d), dt)},
                         "mixer": mixer}},
        "final_norm": {"scale": jnp.ones((d,), dt)},
    }


def mm_highest(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _fp8(x):
    """Per-tensor scaled float8_e4m3 rounding, as an fp8 matmul path does."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def mm_fp8(spec: str, a, b):
    """The control's contraction: both operands rounded to fp8."""
    return mm_highest(spec, _fp8(a), _fp8(b))


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer(cfg: dict, mm: Callable, h, lp):
    m = cost.mamba2_dims(cfg)
    din, n, H, P, K = m["din"], m["n"], m["h"], m["p"], m["k"]
    eps = cfg["norm_eps"]
    B, S, _ = h.shape
    mx = lp["mixer"]
    x = _rmsnorm(h, lp["norm1"]["scale"], eps)
    proj = mm("bsd,de->bse", x, mx["in_proj"])
    z, xbc, dt = proj[..., :din], proj[..., din:2 * din + 2 * n], proj[..., 2 * din + 2 * n:]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = mx["conv_b"] + sum(pad[:, j:j + S, :] * mx["conv_w"][:, j] for j in range(K))
    xbc = jax.nn.silu(conv)
    xs, Bm, Cm = xbc[..., :din], xbc[..., din:din + n], xbc[..., din + n:]
    dt = jax.nn.softplus(dt + mx["dt_bias"])                     # [B, S, H]
    a = dt * (-jnp.exp(mx["A_log"]))                             # [B, S, H]
    cs = jnp.cumsum(a, axis=1)
    seg = cs[:, :, None, :] - cs[:, None, :, :]                  # [B, T, S, H]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = mm("btn,bsn->bts", Cm, Bm)                          # C_t . B_s
    xh = xs.reshape(B, S, H, P)
    y = mm("btsh,bshp->bthp", scores[..., None] * decay, xh * dt[..., None])
    y = y + xh * mx["D"][:, None]
    y = y.reshape(B, S, din) * jax.nn.silu(z)
    y = _rmsnorm(y, mx["norm_scale"], eps)
    return h + mm("bse,ed->bsd", y, mx["out_proj"])


def loss(params: dict, cfg: dict, tokens, labels, mm: Callable = mm_highest,
         token_fraction: float = 1.0):
    """Mean next-token cross-entropy, all in float32; ``token_fraction`` < 1
    averages over only the first positions (a planted fault)."""
    p = jax.tree_util.tree_map(lambda x: x.astype(F32), params)
    h = jnp.take(p["embed"], tokens, axis=0)

    def body(h, lp):
        return jax.checkpoint(lambda h, lp: _layer(cfg, mm, h, lp))(h, lp), None

    h, _ = jax.lax.scan(body, h, p["blocks"]["0"])
    h = _rmsnorm(h, p["final_norm"]["scale"], cfg["norm_eps"])
    logits = mm("bsd,vd->bsv", h, p["embed"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    keep = max(1, int(nll.shape[-1] * token_fraction))
    return jnp.mean(nll[..., :keep])


# ------------------------------------------------------------- training
def leaf_norms(tree) -> jnp.ndarray:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def robust_tree(messages, key, rule: dict):
    """Bucketing + RFA over per-worker trees (leaves [W, ...]) in float32 at
    the highest precision; returns the aggregate tree."""
    leaves = jax.tree_util.tree_leaves(messages)
    W = leaves[0].shape[0]
    M = bucket_matrix(mix_key(key), W, rule["s"])
    ys = jax.tree_util.tree_map(lambda x: mm_highest("mw,w...->m...", jnp.asarray(M, F32), x),
                                messages)
    ms = M.shape[0]
    if ms == 1:
        return jax.tree_util.tree_map(lambda y: y[0], ys)
    d2 = np.zeros((ms, ms))
    for y in jax.tree_util.tree_leaves(ys):
        flat = y.reshape(ms, -1)
        for i in range(ms):
            d2[i] += np.asarray(jnp.sum(jnp.square(flat - flat[i]), axis=1), np.float64)
    c = jnp.asarray(rfa_weights(d2, rule["iters"], rule["eps"]), F32)
    return jax.tree_util.tree_map(lambda y: mm_highest("m,m...->...", c, y), ys)


def train_steps(params0, batches, keys, cfg: dict, train: dict, workers: int,
                mm: Callable = mm_highest, token_fraction: float = 1.0) -> dict:
    """The configuration's training recipe, step by step, in float32:
    per-worker gradients, worker momentum, bucketing + the robust rule,
    AdamW; parameters stored in their served dtypes after every update.

    ``batches`` holds each step's {"tokens", "labels"} of shape [B, S] with
    B split evenly over the workers; each worker's gradient is accumulated
    one sequence at a time. ``token_fraction`` < 1 leaves out the later
    positions of every sequence (a planted fault, for the calibration).
    Returns per-step losses, per-leaf norms of the first aggregated
    gradient, and per-leaf norms of the parameters' change over the steps.
    """
    beta, lr = train["worker_momentum"], train["lr"]
    b1, b2, eps, wd = train["beta1"], train["beta2"], train["eps"], train["weight_decay"]
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss(p, cfg, t[None], l[None], mm, token_fraction)))
    p = params0
    wm = None
    mom = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), p)
    vel = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), p)
    losses, first = [], None

    @jax.jit
    def adamw(p, mom, vel, g, t):
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        mom = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, mom, g)
        vel = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, vel, g)

        def upd(x, m_, v_):
            delta = lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + lr * wd * x.astype(F32)
            return (x.astype(F32) - delta).astype(x.dtype)

        return jax.tree_util.tree_map(upd, p, mom, vel), mom, vel

    for t, (batch, key) in enumerate(zip(batches, keys)):
        tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        per = tokens.shape[0] // workers
        grads_w, loss_w = [], []
        for w in range(workers):
            g_sum, l_sum = None, 0.0
            for r in range(w * per, (w + 1) * per):
                l_r, g_r = grad_fn(p, jnp.asarray(tokens[r]), jnp.asarray(labels[r]))
                l_sum += float(l_r)
                g_sum = g_r if g_sum is None else jax.tree_util.tree_map(jnp.add, g_sum, g_r)
            grads_w.append(jax.tree_util.tree_map(lambda g: g / per, g_sum))
            loss_w.append(l_sum / per)
        losses.append(float(np.mean(loss_w)))
        g_stack = jax.tree_util.tree_map(lambda *g: jnp.stack(g), *grads_w)
        del grads_w
        wm = (jax.tree_util.tree_map(lambda g: (1 - beta) * g, g_stack) if wm is None
              else jax.tree_util.tree_map(lambda m_, g: beta * m_ + (1 - beta) * g, wm, g_stack))
        agg = robust_tree(wm, key, train["rule"])
        if first is None:
            first = np.asarray(leaf_norms(agg), np.float64)
        p, mom, vel = adamw(p, mom, vel, agg, jnp.float32(t + 1))
    delta = jax.tree_util.tree_map(lambda a, b: a.astype(F32) - b.astype(F32), p, params0)
    return {"losses": losses, "grad_norms": first,
            "delta_norms": np.asarray(leaf_norms(delta), np.float64)}
