"""Distributed train / prefill / decode steps (pjit + GSPMD).

Workers = data-parallel mesh groups: the global batch dim is split over the
(pod, data) axes into W worker shards; per-worker gradients come from a
``vmap`` over the worker axis (no cross-worker reduction), then the paper's
mixing + robust aggregation REPLACES the gradient all-reduce
(``robust_gradient_sync`` with the packed flat-buffer engine: one column
reshard in, one reshard out per step, regardless of how many gradient
leaves the architecture has — see repro/distributed/packing.py). Attack simulation is a feature of the
single-host simulation path (repro/training/byzantine.py); the distributed
path runs the defense.

The step's phases carry name scopes (``telemetry.phase(..., layer="step")``):
``step/grad`` (per-worker forward and backward; backward operations show
``transpose(`` in their ``op_name``), ``step/momentum``, ``step/sync`` (the
sync's own ``telemetry/*`` phases nest inside) and ``step/optimizer``. They
touch only HLO metadata; a profiler trace reads device time through them.

Momentum modes:
  worker : Algorithm 2 — per-worker momentum leaves [W, ...] (small/mid archs)
  server : Remark 7 — raw per-worker grads robust-aggregated, momentum in
           the (shardable) optimizer state (giant archs / FSDP).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ByzConfig, InputShape, ModelConfig
from repro.distributed.robust_sync import robust_gradient_sync
from repro.distributed.sharding import (
    batch_spec,
    cache_shardings,
    constrain_worker_tree,
    overrides_from_config,
    param_shardings,
    worker_grad_spec,
)
from repro.launch.mesh import n_workers as mesh_n_workers
from repro.models import transformer as tfm
from repro.optim import make_optimizer
from repro.telemetry import phase


# ------------------------------------------------------------- input specs
def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        if cfg.n_codebooks:
            specs = {
                "tokens": jax.ShapeDtypeStruct((B, cfg.n_codebooks, S), i32),
                "labels": jax.ShapeDtypeStruct((B, cfg.n_codebooks, S), i32),
            }
        else:
            specs = {
                "tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
        if cfg.n_prefix_tokens:
            specs["prefix_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.n_prefix_tokens, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        return specs
    # decode: ONE new token against a seq_len cache
    tok_shape = (B, cfg.n_codebooks) if cfg.n_codebooks else (B,)
    return {"token": jax.ShapeDtypeStruct(tok_shape, i32)}


def batch_shardings(cfg: ModelConfig, shape: InputShape, mesh) -> Dict[str, NamedSharding]:
    bs = batch_spec(mesh)
    specs = input_specs(cfg, shape)
    out = {}
    for k, v in specs.items():
        spec = [None] * len(v.shape)
        nw = mesh_n_workers(mesh)
        if v.shape[0] % nw == 0 and v.shape[0] >= nw:
            spec[0] = bs[0]
        out[k] = NamedSharding(mesh, P(*spec))
    return out


# -------------------------------------------------------------- train step
def make_train_step(
    cfg: ModelConfig,
    byz: ByzConfig,
    mesh,
    lr: float = 1e-3,
    optimizer: str = "sgdm",
    telemetry: bool = False,
) -> Tuple[Callable, Dict[str, Any]]:
    """Returns (step_fn, shardings) where
    step_fn(params, opt_state, worker_m, key, batch) ->
        (params, opt_state, worker_m, metrics).
    ``worker_m`` is a zeros-like stacked tree for momentum_mode=worker, else
    an empty dict. ``shardings`` maps each argument to NamedShardings.

    ``telemetry=True`` adds the sync's device-resident metrics pytree as
    ``metrics["telemetry"]`` (repro/telemetry). The flag is baked into the
    closure, so the step's signature and jit cache are unaffected; with the
    default False the traced program is the seed program.
    """
    W = mesh_n_workers(mesh)
    aggregator = byz.make_aggregator(W)
    opt_init, opt_update = make_optimizer(
        optimizer, lr=lr, beta1=byz.worker_momentum or 0.9,
        m_dtype=cfg.opt_m_dtype,
    )
    use_worker_momentum = cfg.momentum_mode == "worker" and byz.worker_momentum > 0
    is_plain_mean = byz.aggregator in ("mean", "avg") and byz.mixing in ("none", "")

    # Param shardings are needed INSIDE step_fn: for FSDP configs the packed
    # engine's egress unpacks the aggregate directly to each param's
    # NamedSharding instead of materializing a replicated [n_pad] row on
    # every device. Non-FSDP params are (near-)replicated, where per-leaf
    # unpacking just splits the one egress all-gather into many — keep the
    # replicated reshard_out there.
    params_shape = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    params_sh = param_shardings(params_shape, mesh, fsdp=cfg.fsdp,
                                overrides=overrides_from_config(cfg))
    egress_sh = params_sh if cfg.fsdp else None

    def loss_of(params, b):
        return tfm.loss_fn(params, cfg, b)

    def step_fn(params, opt_state, worker_m, key, batch):
        # [B_global, ...] -> [W, b_local, ...]
        def split_workers(x):
            return x.reshape((W, x.shape[0] // W) + x.shape[1:])

        wbatch = jax.tree_util.tree_map(split_workers, batch)

        if is_plain_mean and not use_worker_momentum:
            # BASELINE: standard data-parallel mean gradient (the paper's Avg).
            def mean_loss(p):
                loss, aux = jax.vmap(lambda b: loss_of(p, b))(wbatch)
                return jnp.mean(loss), aux

            with phase("grad", layer="step"):
                (loss, aux), grads = jax.value_and_grad(mean_loss, has_aux=True)(params)
            agg_grads = grads
            info = {}
        else:
            def one_worker(b):
                (loss, aux), g = jax.value_and_grad(loss_of, has_aux=True)(params, b)
                return g, loss

            with phase("grad", layer="step"):
                grads_w, losses = jax.vmap(one_worker)(wbatch)
                loss = jnp.mean(losses)
            if use_worker_momentum:
                beta = byz.worker_momentum
                with phase("momentum", layer="step"):
                    worker_m = jax.tree_util.tree_map(
                        lambda m, g: beta * m + (1.0 - beta) * g.astype(jnp.float32),
                        worker_m,
                        grads_w,
                    )
                messages = worker_m
            else:
                messages = grads_w
            with phase("sync", layer="step"):
                agg_grads, info = robust_gradient_sync(
                    messages, aggregator, key=key, mesh=mesh, engine="packed",
                    out_shardings=egress_sh, telemetry=telemetry,
                )

        with phase("optimizer", layer="step"):
            params, opt_state = opt_update(agg_grads, opt_state, params)
        metrics = {"loss": loss}
        if telemetry and "telemetry" in info:
            metrics["telemetry"] = info["telemetry"]
        return params, opt_state, worker_m, metrics

    # ----- shardings (params_sh computed above, before step_fn)
    opt_shape = jax.eval_shape(opt_init, params_shape)
    # optimizer moments mirror param shardings; step counter replicated
    opt_sh = _opt_state_shardings(opt_shape, params_sh, mesh)
    if use_worker_momentum:
        wm_shape = jax.eval_shape(
            lambda p: jax.tree_util.tree_map(
                lambda x: jnp.zeros((W,) + x.shape, jnp.float32), p
            ),
            params_shape,
        )
        wm_sh = jax.tree_util.tree_map(lambda sh: worker_grad_spec(sh, mesh), params_sh)
    else:
        wm_shape, wm_sh = {}, {}

    shardings = {
        "params": params_sh,
        "opt_state": opt_sh,
        "worker_m": wm_sh,
        "params_shape": params_shape,
        "opt_shape": opt_shape,
        "wm_shape": wm_shape,
        "replicated": NamedSharding(mesh, P()),
    }
    return step_fn, shardings


def _opt_state_shardings(opt_shape, params_sh, mesh):
    """OptState(step, m, v): moments mirror params; step replicated."""
    rep = NamedSharding(mesh, P())

    def mirror(tree):
        if tree is None:
            return None
        return jax.tree_util.tree_map(lambda _, sh: sh, tree, params_sh)

    return type(opt_shape)(step=rep, m=mirror(opt_shape.m), v=mirror(opt_shape.v))


# ------------------------------------------------------------ prefill step
def make_prefill_step(cfg: ModelConfig, mesh, last_only: bool = True) -> Callable:
    """Serving prefill. ``last_only`` (default) unembeds ONLY the final
    position — the next-token logits a server actually needs. Materializing
    full-sequence fp32 logits is a [B, S, V] tensor (67 GB/device for
    gemma-7b at prefill_32k) that dominated peak memory."""

    def prefill(params, batch):
        h, _ = tfm.forward_hidden(
            params, cfg, batch["tokens"], prefix_embeds=batch.get("prefix_embeds")
        )
        if last_only:
            h = h[:, -1:]
        return tfm.unembed(params, cfg, h)

    return prefill


# ------------------------------------------------------------- decode step
def make_serve_step(cfg: ModelConfig, mesh, shape: InputShape) -> Tuple[Callable, Any, Any]:
    """Returns (serve_fn(params, cache, token, position) -> (logits, cache),
    cache_shape (ShapeDtypeStructs), cache_sharding)."""
    B = shape.global_batch

    def serve(params, cache, token, position):
        return tfm.decode_step(params, cfg, cache, token, position)

    cache_shape = jax.eval_shape(lambda: tfm.init_cache(cfg, B, shape.seq_len))
    cache_sh = cache_shardings(cache_shape, mesh, B)
    return serve, cache_shape, cache_sh
