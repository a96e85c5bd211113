"""Byzantine-robust gradient synchronization — the paper's technique as the
distributed gradient sync (replaces the mean all-reduce across workers).

Factorized Gram-space implementation (DESIGN.md §4): the stacked
``[n_workers, n_params]`` matrix never exists as a per-worker gather. The
three phases, all linear in the inputs:

  stats phase   : Gram matrix G = einsum('wn,vn->wv', X, X) — a tiny
                  [W, W] replicated array.
  coeff phase   : mixing (bucketing/resampling) composes linearly
                  (G_y = M G M^T) and Krum/RFA/CCLIP run in coefficient
                  space — O(W^2) work on the [W, W] matrix.
  combine phase : out = einsum('w,wn->n', M^T c, X).

Coordinatewise rules (CM / trimmed mean) skip the stats phase: mixing is a
tiny matmul over the worker axis and the median runs column-locally —
exactly equal to the stacked semantics.

COLLECTIVE SCHEDULE (the systems-critical part, EXPERIMENTS.md §Perf):
naively, the worker axis of a leaf lives on the (pod, data) mesh axes, so
GSPMD resolves the cross-worker contractions by ALL-GATHERING the full
fp32 ``[W, N]`` stack onto every device — W x params x 4 bytes of ICI
traffic (74 GB/chip/step for tinyllama, 70 TB for kimi-k2). Both engines
here instead force a COLUMN resharding first: an all-to-all that lays the
flattened parameter dimension across ALL mesh axes with the worker axis
replicated, so each device holds an identical-worker column slice, computes
its partial Gram locally, and a [W, W] all-reduce finishes the stats phase.

PACKED SCHEDULE (default, ``engine="packed"``): the whole gradient pytree
is flattened ONCE into a padded ``[W, N_pad]`` fp32 buffer (layout cached
per tree structure — repro/distributed/packing.py), column-resharded ONCE,
run through the Pallas kernels (pairwise_gram / bucket_mix / cwise_median)
on the packed buffer — shard_map-partitioned on multi-device meshes, each
device running the kernel on its local column slice with an explicit psum
for the Gram phase (repro/distributed/shard_kernels.py) — then egressed
ONCE: either a replicated reshard-out, or, with ``out_shardings``, a
param-sharded unpack that never materializes the replicated ``[N_pad]``
row (the FSDP egress). One ingress + one egress and one kernel launch per
phase PER SYNC, regardless of leaf count. Traffic ~= 2x gradient bytes.

PER-LEAF SCHEDULE (``engine="per_leaf"``, this module): the legacy
fallback, kept as the bit-exactness oracle for the packed engine. Each leaf
is resharded, upcast, and contracted separately: the same 2x-bytes traffic
total, but split into TWO collectives and several kernel launches PER LEAF
per step (stats + combine) — hundreds of small all-to-alls per round on a
transformer, which is what the packed engine eliminates. With
``use_kernels=True`` its Gram phase chains through the same Pallas kernel
blocks as the packed engine (``acc`` + ``full_blocks``) on each leaf's
lane-aligned ``[W, R * C']`` view (``packing.to_lanes``, the layout of the
leaf's segment in the packed buffer), making the two engines
bit-identical (asserted in tests/test_packing.py); with the default
``use_kernels=False`` it is the pure-jnp GSPMD path.

Semantics are equal to ``RobustAggregator(...)`` on the stacked vector
(verified in tests/test_robust_sync.py) — sharding constraints never change
values. The collective schedule itself (one ingress + one egress, kernel
route taken, no replicated egress row) is regression-gated by
``python -m repro.analysis``, which compiles this sync on the 8-device
host mesh and checks it against committed per-target collective budgets
(docs/static_analysis.md).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.aragg import RobustAggregator
from repro.distributed import packing
from repro.kernels import ops


def _leaf32(x):
    return x.astype(jnp.float32)


def _colshard(flat: jnp.ndarray, mesh) -> jnp.ndarray:
    """Reshard a [W, N_leaf] stack: worker axis replicated, N over ALL mesh
    axes (an all-to-all; see module docstring). No-op without a mesh (the
    single-host simulation path)."""
    if mesh is None:
        return flat
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(mesh.axis_names)
    return jax.lax.with_sharding_constraint(
        flat, NamedSharding(mesh, P(None, axes if len(axes) > 1 else axes[0]))
    )


def tree_gram(grads_w: Any, n_workers: int, mesh=None, use_kernels: bool = False,
              block_d: int = 2048) -> jnp.ndarray:
    """Sum over leaves of per-leaf worker Gram matrices -> [W, W] fp32.

    With ``use_kernels`` the per-leaf contributions chain through the Pallas
    Gram kernel with fixed ``block_d`` blocks and a carried accumulator —
    the exact block-dot sequence of the packed engine (bit-exactness)."""
    gram = jnp.zeros((n_workers, n_workers), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(grads_w):
        if leaf.size == 0:
            continue
        flat = _colshard(packing.to_lanes(leaf), mesh)
        if use_kernels:
            gram = ops.gram(flat, acc=gram, block_d=block_d, full_blocks=True)
        else:
            flat = _leaf32(flat)
            gram = gram + flat @ flat.T
    return gram


def tree_combine(grads_w: Any, weights: jnp.ndarray, mesh=None,
                 use_kernels: bool = False, block_d: int = 2048) -> Any:
    """Per-leaf weighted combination over the worker axis."""
    def one(leaf):
        if leaf.size == 0:  # guard BEFORE reshape(W, -1) / reshard
            return jnp.zeros(leaf.shape[1:], leaf.dtype)
        flat = _colshard(packing.to_lanes(leaf), mesh)
        if use_kernels:
            out = ops.mix_apply(weights[None, :], flat, block_d=block_d)[0]
        else:
            out = weights @ _leaf32(flat)
        return packing.from_lanes(out, leaf.shape[1:]).astype(leaf.dtype)

    return jax.tree_util.tree_map(one, grads_w)


def tree_mix(grads_w: Any, mix_matrix: jnp.ndarray, mesh=None,
             use_kernels: bool = False, block_d: int = 2048) -> Any:
    """Apply the mixing operator leaf-wise: [W, ...] -> [m, ...]."""
    def one(leaf):
        if leaf.size == 0:  # guard BEFORE reshape(W, -1) / reshard
            return jnp.zeros((mix_matrix.shape[0],) + leaf.shape[1:], leaf.dtype)
        flat = _colshard(packing.to_lanes(leaf), mesh)
        if use_kernels:
            out = ops.mix_apply(mix_matrix, flat, block_d=block_d)
        else:
            out = mix_matrix @ _leaf32(flat)
        return packing.from_lanes(out, leaf.shape[1:]).astype(leaf.dtype)

    return jax.tree_util.tree_map(one, grads_w)


def _per_leaf_sync(
    grads_w: Any,
    aggregator: RobustAggregator,
    key: Optional[jax.Array],
    mesh,
    use_kernels: bool,
    block_d: int,
    telemetry: bool = False,
) -> Tuple[Any, dict]:
    """The per-leaf fallback engine (two collectives per leaf; docstring).

    ``telemetry=True`` adds ``info["telemetry"]`` from the Gram-space probes
    (non-coordinatewise rules only — the coordinatewise route has no stacked
    buffer to probe without materializing one; use the packed engine for
    CM/TM telemetry)."""
    leaves = jax.tree_util.tree_leaves(grads_w)
    n_workers = leaves[0].shape[0]
    info: dict = {}

    if aggregator.base.coordinatewise:
        mix_key = None if key is None else jax.random.split(key)[0]
        m = aggregator.mixer.matrix(mix_key, n_workers)
        if not use_kernels:
            mixed = tree_mix(grads_w, m, mesh=mesh)
            out = jax.tree_util.tree_map(
                lambda leaf: aggregator.base.combine_leaf(leaf), mixed
            )
            return out, info

        # kernel route: fp32 end-to-end per leaf, CM through the median
        # kernel — mirrors the packed engine phase for phase.
        def one(leaf):
            if leaf.size == 0:  # guard BEFORE reshape(W, -1) / reshard
                return jnp.zeros(leaf.shape[1:], leaf.dtype)
            flat = _colshard(packing.to_lanes(leaf), mesh)
            mixed = ops.mix_apply(m, flat, block_d=block_d)
            if aggregator.base.name == "cm":
                out = ops.cm_aggregate(mixed, block_d=block_d)
            elif aggregator.base.name == "tm":
                b = min(aggregator.base.n_trim, (mixed.shape[0] - 1) // 2)
                out = ops.tm_aggregate(mixed, b, block_d=block_d)
            else:
                out = aggregator.base.combine_leaf(mixed)
            return packing.from_lanes(out, leaf.shape[1:]).astype(leaf.dtype)

        return jax.tree_util.tree_map(one, grads_w), info

    gram = tree_gram(grads_w, n_workers, mesh=mesh, use_kernels=use_kernels,
                     block_d=block_d)
    if telemetry:
        weights, stats = aggregator.worker_weights_and_stats_from_gram(
            gram, key=key)
        info["telemetry"] = stats
    else:
        weights = aggregator.worker_weights_from_gram(gram, key=key)
    info["agg_weights"] = weights
    info["gram_diag_mean"] = jnp.mean(jnp.diagonal(gram))
    combined = tree_combine(grads_w, weights, mesh=mesh,
                            use_kernels=use_kernels, block_d=block_d)
    return combined, info


def robust_gradient_sync(
    grads_w: Any,
    aggregator: RobustAggregator,
    key: Optional[jax.Array] = None,
    mesh=None,
    engine: str = "packed",
    block_d: int = 2048,
    use_kernels: Optional[bool] = None,
    out_shardings: Any = None,
    telemetry: bool = False,
) -> Tuple[Any, dict]:
    """Aggregate per-worker gradient trees (leaves ``[W, ...]``) into one
    gradient tree, using mixing + the robust rule. Returns (grads, info).

    ``engine="packed"`` (default) runs the single-buffer engine
    (repro/distributed/packing.py); ``engine="per_leaf"`` is the legacy
    fallback and bit-exactness oracle. ``use_kernels=None`` resolves to the
    Pallas route on every mesh for the packed engine (shard_map-partitioned
    on multi-device), and to pure jnp for the per-leaf engine.
    ``out_shardings`` (NamedSharding tree matching the gradients sans
    worker axis) selects the param-sharded egress. ``telemetry=True`` adds
    the device-resident metrics pytree as ``info["telemetry"]``; the
    default False traces the seed program exactly (repro/telemetry)."""
    if engine == "packed":
        return packing.packed_robust_sync(
            grads_w, aggregator, key=key, mesh=mesh, block_d=block_d,
            use_kernels=use_kernels, out_shardings=out_shardings,
            telemetry=telemetry,
        )
    if engine != "per_leaf":
        raise ValueError(f"unknown sync engine {engine!r}")
    out, info = _per_leaf_sync(grads_w, aggregator, key, mesh,
                               bool(use_kernels), block_d,
                               telemetry=telemetry)
    if out_shardings is not None and mesh is not None:
        out = jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, out, out_shardings)
    return out, info
