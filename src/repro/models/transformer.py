"""Generic pattern-scanned decoder LM covering all assigned families.

A model is a repeating *pattern* of layers (``cfg.pattern_``), each layer a
(mixer, ff) pair with mixer in {attn, ssm} and ff in {mlp, moe, none}. The
``n_layers = period * n_periods`` stack is executed with ``lax.scan`` over
periods (params stacked on a leading period axis), which keeps HLO size and
compile time flat in depth — essential for the 61-layer dry-run configs.

Families:
  dense   pattern [(attn, mlp)]
  moe     pattern [(attn, moe)]
  ssm     pattern [(ssm, none)]
  hybrid  jamba-style period mixing attn/ssm layers and moe/mlp ffs
  vlm     dense/moe LM consuming stub patch embeddings as a prefix
  audio   musicgen: K codebook embeddings summed, K output heads

Entry points:
  init_params(cfg, key)                      -> params pytree
  forward(params, cfg, tokens, ...)          -> logits, aux
  loss_fn(params, cfg, batch)                -> scalar loss, aux
  init_cache(cfg, batch, cache_len)          -> decode cache
  decode_step(params, cfg, cache, token, pos)-> logits, new cache
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import embed_init, dense_init, init_rmsnorm, init_mlp_block, mlp_block, rmsnorm
from repro.telemetry import phase


# ================================================================== params
def _init_layer(key, mixer: str, ff: str, cfg) -> Dict[str, Any]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dtype = jnp.dtype(cfg.dtype)
    p: Dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    if mixer == "attn":
        p["mixer"] = attn_mod.init_attention(k1, cfg)
    elif mixer == "ssm":
        p["mixer"] = ssm_mod.init_ssm(k1, cfg)
    else:
        raise ValueError(mixer)
    if ff != "none":
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        if ff == "mlp":
            p["ff"] = init_mlp_block(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind, dtype)
        elif ff == "moe":
            p["ff"] = moe_mod.init_moe(k2, cfg)
        else:
            raise ValueError(ff)
    return p


def _init_period(key, cfg) -> Dict[str, Any]:
    keys = jax.random.split(key, len(cfg.pattern_))
    return {
        str(i): _init_layer(keys[i], mixer, ff, cfg)
        for i, (mixer, ff) in enumerate(cfg.pattern_)
    }


def init_params(cfg, key) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    params: Dict[str, Any] = {}
    n_books = max(1, cfg.n_codebooks)
    if cfg.n_codebooks:
        ks = jax.random.split(k_embed, n_books)
        params["embed"] = jnp.stack(
            [embed_init(k, cfg.vocab_size, cfg.d_model, dtype) for k in ks]
        )  # [K, V, D]
    else:
        params["embed"] = embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype)

    period_keys = jax.random.split(k_blocks, cfg.n_periods)
    params["blocks"] = jax.vmap(lambda k: _init_period(k, cfg))(period_keys)
    params["final_norm"] = init_rmsnorm(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks:
            ks = jax.random.split(k_head, n_books)
            params["lm_head"] = jnp.stack(
                [dense_init(k, cfg.d_model, cfg.vocab_size, dtype) for k in ks]
            )  # [K, D, V]
        else:
            params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
    return params


# ================================================================== embed
def embed_tokens(params, cfg, tokens) -> jnp.ndarray:
    if cfg.n_codebooks:
        # tokens: [B, K, S]; embed: [K, V, D] -> sum over codebooks
        embs = jax.vmap(lambda be, t: jnp.take(be, t, axis=0), in_axes=(0, 1))(
            params["embed"], tokens
        )  # [K, B, S, D]
        return jnp.sum(embs, axis=0)
    return jnp.take(params["embed"], tokens, axis=0)


def unembed(params, cfg, h) -> jnp.ndarray:
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,kdv->bskv", h, params["lm_head"])
    elif cfg.tie_embeddings:
        logits = h @ params["embed"].T
    else:
        logits = h @ params["lm_head"]
    logits = logits.astype(jnp.float32)
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


# ================================================================= forward
def _layer_apply(lp, h, mixer: str, ff: str, cfg, positions, aux_acc):
    h = h + (
        attn_mod.attention(lp["mixer"], rmsnorm(lp["norm1"], h, cfg.norm_eps), cfg, positions)
        if mixer == "attn"
        else ssm_mod.ssm_layer(lp["mixer"], rmsnorm(lp["norm1"], h, cfg.norm_eps), cfg)
    )
    if ff == "mlp":
        h = h + mlp_block(lp["ff"], rmsnorm(lp["norm2"], h, cfg.norm_eps), cfg.mlp_kind)
    elif ff == "moe":
        out, aux = moe_mod.moe_layer(lp["ff"], rmsnorm(lp["norm2"], h, cfg.norm_eps), cfg)
        h = h + out
        aux_acc = {k: aux_acc.get(k, 0.0) + v for k, v in aux.items()}
    return h, aux_acc


def forward_hidden(
    params,
    cfg,
    tokens,
    prefix_embeds: Optional[jnp.ndarray] = None,
    positions: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Backbone only: final-norm hidden states [B, S, D] (token positions
    only) + aux. Callers choose which positions to unembed — the serving
    prefill unembeds just the last position, which keeps the [B, S, V] fp32
    logits tensor (e.g. 67 GB/device for gemma-7b prefill_32k) from ever
    existing."""
    with phase("embed", layer="model"):
        h = embed_tokens(params, cfg, tokens)
    n_prefix = 0
    if prefix_embeds is not None:
        n_prefix = prefix_embeds.shape[1]
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    S = h.shape[1]
    if positions is None:
        positions = jnp.arange(S)[None, :]

    def period_body(carry, period_params):
        h, aux = carry
        for i, (mixer, ff) in enumerate(cfg.pattern_):
            h, aux = _layer_apply(period_params[str(i)], h, mixer, ff, cfg, positions, aux)
        return (h, aux), None

    if cfg.remat == "full":
        period_body = jax.checkpoint(period_body)

    aux0 = {"moe_lb_loss": jnp.zeros((), jnp.float32),
            "moe_z_loss": jnp.zeros((), jnp.float32),
            "moe_drop_frac": jnp.zeros((), jnp.float32)}
    has_moe = any(ff == "moe" for _, ff in cfg.pattern_)
    if not has_moe:
        aux0 = {}
    (h, aux), _ = jax.lax.scan(
        period_body, (h, aux0), params["blocks"], unroll=min(cfg.scan_unroll, cfg.n_periods)
    )
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if n_prefix:
        h = h[:, n_prefix:]
    return h, aux


def forward(
    params,
    cfg,
    tokens,
    prefix_embeds: Optional[jnp.ndarray] = None,
    positions: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Train forward: full-sequence logits. tokens: [B, S] ([B, K, S] for
    codebooks); prefix_embeds: [B, n_prefix, D] stub modality embeddings."""
    h, aux = forward_hidden(params, cfg, tokens, prefix_embeds, positions)
    logits = unembed(params, cfg, h)
    return logits, aux


def loss_fn(params, cfg, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross-entropy. batch: dict with "tokens", "labels",
    optional "prefix_embeds". labels use -100 as the ignore index."""
    h, aux = forward_hidden(
        params, cfg, batch["tokens"], prefix_embeds=batch.get("prefix_embeds")
    )
    with phase("unembed", layer="model"):
        logits = unembed(params, cfg, h)
        labels = batch["labels"]
        if cfg.n_codebooks:
            # logits [B,S,K,V], labels [B,K,S]
            labels = jnp.moveaxis(labels, 1, 2)  # [B,S,K]
        valid = labels != -100
        labels_c = jnp.clip(labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels_c[..., None], axis=-1)[..., 0]
        loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
    if aux:
        loss = loss + cfg.router_aux_coef * aux["moe_lb_loss"] + cfg.router_z_coef * aux["moe_z_loss"]
    aux = dict(aux)
    aux["ce_loss"] = loss
    return loss, aux


# ================================================================== decode
def cache_length(cfg, seq_len: int) -> int:
    if cfg.long_context == "state":
        return 0
    if cfg.long_context == "window" and seq_len > cfg.long_context_window:
        return cfg.long_context_window
    if cfg.sliding_window:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def init_cache(cfg, batch: int, seq_len: int) -> Dict[str, Any]:
    """Stacked decode cache: one entry per pattern index, leading period axis."""
    dtype = jnp.dtype(cfg.dtype)
    L = cache_length(cfg, seq_len)
    cache: Dict[str, Any] = {}
    for i, (mixer, _) in enumerate(cfg.pattern_):
        if mixer == "attn":
            one = attn_mod.init_kv_cache(batch, max(L, 1), cfg, dtype)
        else:
            one = ssm_mod.init_ssm_cache(batch, cfg, dtype)
        cache[str(i)] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape), one
        )
    return cache


def decode_step(params, cfg, cache, token, position) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One-token decode. token: [B] (or [B, K]); position: scalar int32.
    Returns (logits [B, V] or [B, K, V], new cache)."""
    if cfg.n_codebooks:
        # token: [B, K]; embed: [K, V, D]
        embs = jax.vmap(lambda be, t: jnp.take(be, t, axis=0), in_axes=(0, 1))(
            params["embed"], token
        )  # [K, B, D]
        h = jnp.sum(embs, axis=0)[:, None, :]
    else:
        h = jnp.take(params["embed"], token, axis=0)[:, None, :]

    def period_body(h, xs):
        period_params, period_cache = xs
        new_cache = {}
        for i, (mixer, ff) in enumerate(cfg.pattern_):
            lp = period_params[str(i)]
            x = rmsnorm(lp["norm1"], h, cfg.norm_eps)
            if mixer == "attn":
                out, nc = attn_mod.decode_attention(lp["mixer"], x, period_cache[str(i)], cfg, position)
            else:
                out, nc = ssm_mod.decode_ssm(lp["mixer"], x, period_cache[str(i)], cfg)
            new_cache[str(i)] = nc
            h = h + out
            if ff == "mlp":
                h = h + mlp_block(lp["ff"], rmsnorm(lp["norm2"], h, cfg.norm_eps), cfg.mlp_kind)
            elif ff == "moe":
                out, _ = moe_mod.moe_layer(lp["ff"], rmsnorm(lp["norm2"], h, cfg.norm_eps), cfg)
                h = h + out
        return h, new_cache

    h, new_cache = jax.lax.scan(
        period_body, h, (params["blocks"], cache), unroll=min(cfg.scan_unroll, cfg.n_periods)
    )
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = unembed(params, cfg, h)  # [B, 1, ...]
    return logits[:, 0], new_cache
