"""Packed flat-buffer robust-aggregation engine.

The per-leaf sync path (repro/distributed/robust_sync.py) pays a per-leaf
tax that dwarfs the aggregation math: every gradient leaf is resharded (an
all-to-all), upcast, and matmul'd twice per step (stats + combine), so a
transformer with hundreds of leaves issues hundreds of small collectives
and kernel launches per round. Mixing, the Gram stats phase, and the
combine are all LINEAR in the inputs, so the whole stats -> coeff ->
combine pipeline runs unchanged on one packed ``[W, N_pad]`` fp32 buffer
with exactly ONE reshard in and ONE reshard out per sync — this also covers
NNM-style pre-aggregation (Allouah et al., *Fixing by Mixing*, 2023), which
is just another row-stochastic mixing operator.

``GradPacker`` owns the layout: treedef, per-leaf shapes/dtypes, and column
offsets, computed once per tree structure and cached (``packer_for``). Each
leaf's segment is padded up to a ``block_d`` multiple. That per-leaf
alignment is what makes the packed engine BIT-IDENTICAL to the per-leaf
oracle: the Gram kernel (kernels/pairwise_gram.py) accumulates fixed
``[W, block_d]`` block dots in column order, so one call over the packed
buffer performs the exact same sequence of fp32 operations as the oracle's
chain of per-leaf calls (seeded via the kernel's ``acc`` input). Mixing and
combine reduce over the (tiny, zero-padded) worker axis per column, which
is insensitive to column blocking. Asserted in tests/test_packing.py.

COLLECTIVE SCHEDULE: ``reshard_in`` lays the packed parameter dimension
across ALL mesh axes with the worker axis replicated (one all-to-all);
every device then computes on its identical-worker ``[W, N_pad/n_dev]``
slice (partial Gram + one [W, W] all-reduce). The egress has two modes:
``reshard_out`` replicates the combined ``[N_pad]`` row (one collective)
before unpacking — right when the consumer is replicated (the single-host
simulation, the flat-stack server path); or, given ``out_shardings`` (the
params' NamedShardings from ``sharding.param_shardings``), each leaf is
sliced straight out of the still-column-sharded row and constrained to its
param's sharding, so the fully-replicated ``[N_pad]`` intermediate never
materializes — the tail collective for FSDP configs becomes per-leaf
reshards sized by what each device actually keeps. Either way the schedule
is one ingress + one egress per sync REGARDLESS of leaf count.

Kernels vs GSPMD: the Pallas kernels now run on EVERY mesh. On a trivial
mesh (absent or single-device — the single-host simulation, tests and
benchmarks) the phases call the kernels directly (``kernels/ops.py``); on a
multi-device mesh they route through ``shard_map`` wrappers
(``distributed/shard_kernels.py``) — each device runs the kernel on its
local column slice, with an explicit psum only for the Gram/norms phases —
because ``pallas_call`` is opaque to GSPMD and would otherwise not
partition. On multi-device meshes RFA and CCLIP additionally skip the
[W, W] Gram detour and run the FUSED sharded compositions
(``shard_kernels.rfa_aggregate`` / ``cclip_aggregate``): mix once in
vector space, then one local fused kernel pass + one [W]-sized psum per
iteration. ``use_kernels=False`` selects the plain ``jnp`` contractions
that GSPMD partitions across the column sharding (the numerics reference
for the shard_map path, tests/test_shard_engine.py).

The schedule invariants above are machine-checked: ``repro.analysis``
compiles packed-sync programs on the 8-device host mesh and fails CI if
the kernel route silently falls back to jnp (``jaxpr-pallas-missing``),
the replicated ``f32[n_pad]`` row reappears in a param-sharded-egress
program (``hlo-replicated-egress``), or the collective count/byte
schedule drifts past the committed budgets in ``analysis/budgets/``
(docs/static_analysis.md).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.aragg import RobustAggregator
from repro.distributed import shard_kernels
from repro.kernels import ops
from repro.telemetry import InflightMetrics, phase
from repro.telemetry import probes as _probes


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


class GradPacker:
    """Flattens a per-worker gradient pytree (leaves ``[W, ...]``) into one
    padded ``[W, n_pad]`` fp32 buffer and back. Layout is static per tree
    structure; build instances via ``packer_for`` to get caching."""

    def __init__(self, treedef, leaf_shapes: Tuple[tuple, ...],
                 leaf_dtypes: tuple, block_d: int = 2048):
        if block_d % 128:
            raise ValueError(f"block_d must be a multiple of 128, got {block_d}")
        self.treedef = treedef
        self.leaf_shapes = tuple(tuple(s) for s in leaf_shapes)  # sans worker axis
        self.leaf_dtypes = tuple(jnp.dtype(d) for d in leaf_dtypes)
        self.block_d = int(block_d)
        self.sizes = tuple(math.prod(s) for s in self.leaf_shapes)
        # each leaf segment is padded to a block_d multiple so kernel blocks
        # never straddle leaves (the bit-exactness alignment, module docstring)
        self.padded = tuple(_round_up(z, block_d) if z else 0 for z in self.sizes)
        self.offsets = tuple(
            sum(self.padded[:i]) for i in range(len(self.padded))
        )
        self.n_params = sum(self.sizes)
        self.n_pad = sum(self.padded)

    # ------------------------------------------------------------------ pack
    def pack(self, grads_w: Any) -> jnp.ndarray:
        """Stacked tree (leaves ``[W, ...]``) -> packed ``[W, n_pad]`` fp32.

        Writes each segment into a zeros buffer with dynamic_update_slice —
        under jit XLA aliases the updates in place, so pack costs one pass
        over the gradient bytes. (A concatenate of interleaved data/zero
        pieces is 20x slower on CPU XLA at transformer leaf counts.)"""
        leaves = jax.tree_util.tree_leaves(grads_w)
        W = leaves[0].shape[0]
        buf = jnp.zeros((W, self.n_pad), jnp.float32)
        for leaf, size, off in zip(leaves, self.sizes, self.offsets):
            if size == 0:
                continue
            piece = leaf.reshape(W, size).astype(jnp.float32)
            buf = jax.lax.dynamic_update_slice(buf, piece, (0, off))
        return buf

    # ---------------------------------------------------------------- unpack
    def unpack(self, vec: jnp.ndarray) -> Any:
        """Packed row ``[n_pad]`` -> gradient tree (original shapes/dtypes)."""
        leaves = [
            vec[off : off + size].reshape(shape).astype(dtype)
            for off, size, shape, dtype in zip(
                self.offsets, self.sizes, self.leaf_shapes, self.leaf_dtypes
            )
        ]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def unpack_stacked(self, buf: jnp.ndarray) -> Any:
        """Packed stack ``[k, n_pad]`` -> tree with the leading axis kept."""
        k = buf.shape[0]
        leaves = [
            buf[:, off : off + size].reshape((k,) + shape).astype(dtype)
            for off, size, shape, dtype in zip(
                self.offsets, self.sizes, self.leaf_shapes, self.leaf_dtypes
            )
        ]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"GradPacker(n_leaves={len(self.sizes)}, n_params={self.n_params}, "
                f"n_pad={self.n_pad}, block_d={self.block_d})")


_PACKER_CACHE: Dict[tuple, GradPacker] = {}


def packer_for(grads_w: Any, block_d: int = 2048) -> GradPacker:
    """Layout-cached ``GradPacker`` for this tree structure (leaves carry a
    leading worker axis that is NOT part of the layout)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads_w)
    key = (
        treedef,
        tuple(tuple(l.shape[1:]) for l in leaves),
        tuple(jnp.dtype(l.dtype) for l in leaves),
        int(block_d),
    )
    packer = _PACKER_CACHE.get(key)
    if packer is None:
        packer = GradPacker(treedef, key[1], key[2], block_d=block_d)
        _PACKER_CACHE[key] = packer
    return packer


# -------------------------------------------------------------- collectives
def reshard_in(buf: jnp.ndarray, mesh) -> jnp.ndarray:
    """The ONE ingress collective per sync: lay the packed parameter columns
    across ALL mesh axes, worker axis replicated (an all-to-all). No-op
    without a mesh (the single-host simulation path)."""
    if mesh is None:
        return buf
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(mesh.axis_names)
    return jax.lax.with_sharding_constraint(
        buf, NamedSharding(mesh, P(None, axes if len(axes) > 1 else axes[0]))
    )


def reshard_out(vec: jnp.ndarray, mesh) -> jnp.ndarray:
    """Replicated egress: one collective replicating the combined packed row
    so unpacking (and a replicated consumer) see local values. For sharded
    consumers prefer ``unpack_to_shardings`` (no replicated intermediate)."""
    if mesh is None:
        return vec
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.lax.with_sharding_constraint(vec, NamedSharding(mesh, P()))


def unpack_to_shardings(packer: GradPacker, vec: jnp.ndarray,
                        out_shardings: Any) -> Any:
    """Param-sharded egress: slice each leaf straight out of the (still
    column-sharded) combined row and constrain it to its param's
    ``NamedSharding`` — the fully-replicated ``[n_pad]`` buffer of
    ``reshard_out`` never materializes, and GSPMD emits per-leaf reshards
    sized by what each device actually keeps (the FSDP win)."""
    shardings = jax.tree_util.tree_leaves(out_shardings)
    if len(shardings) != len(packer.sizes):
        raise ValueError(
            f"out_shardings has {len(shardings)} leaves for a "
            f"{len(packer.sizes)}-leaf layout")
    leaves = [
        jax.lax.with_sharding_constraint(
            vec[off:off + size].reshape(shape).astype(dtype), sh)
        for off, size, shape, dtype, sh in zip(
            packer.offsets, packer.sizes, packer.leaf_shapes,
            packer.leaf_dtypes, shardings)
    ]
    return jax.tree_util.tree_unflatten(packer.treedef, leaves)


def _mesh_is_trivial(mesh) -> bool:
    return mesh is None or mesh.devices.size == 1


# ------------------------------------------------------------------- engine
def packed_robust_sync(
    grads_w: Any,
    aggregator: RobustAggregator,
    key: Optional[jax.Array] = None,
    mesh=None,
    block_d: int = 2048,
    use_kernels: Optional[bool] = None,
    out_shardings: Any = None,
    telemetry: bool = False,
) -> Tuple[Any, dict]:
    """Aggregate per-worker gradient trees (leaves ``[W, ...]``) into one
    gradient tree on a single packed buffer. Returns ``(grads, info)``.

    Semantics match the per-leaf path and ``RobustAggregator`` on the
    stacked vector; with kernels on a trivial mesh, the result is
    bit-identical to the per-leaf kernel oracle (tests/test_packing.py).
    ``use_kernels=None`` resolves to the kernel route on EVERY mesh
    (shard_map-partitioned on multi-device — module docstring); pass
    ``False`` for the plain-jnp GSPMD path. ``out_shardings`` (a tree of
    ``NamedSharding`` matching ``grads_w`` sans worker axis) selects the
    param-sharded egress instead of the replicated one.

    ``telemetry=True`` adds ``info["telemetry"]`` — a device-resident
    metrics pytree (clip fractions, Weiszfeld residuals, Krum scores, trim
    masks, per-bucket dispersion, layout counters; repro/telemetry) riding
    out as ordinary outputs. With the default False the traced program is
    the SEED program: bit-exact outputs and byte-identical collective
    budgets, machine-checked by the ``sync_telemetry_off_*`` analysis
    target. The ``jax.named_scope`` phase markers are always on — they
    annotate HLO metadata only and add zero operations.

    The sync's matmuls run at ``highest`` precision: on a TPU the default
    rounds fp32 operands to bf16, and the Gram-space coefficients are
    differences of Gram entries that bf16 rounding moves by ~1e-3."""
    with jax.default_matmul_precision("highest"):
        return _packed_robust_sync(grads_w, aggregator, key, mesh, block_d,
                                   use_kernels, out_shardings, telemetry)


def _packed_robust_sync(grads_w, aggregator, key, mesh, block_d,
                        use_kernels, out_shardings, telemetry):
    packer = packer_for(grads_w, block_d=block_d)
    leaves = jax.tree_util.tree_leaves(grads_w)
    W = leaves[0].shape[0]
    if packer.n_params == 0:  # degenerate all-empty tree
        return packer.unpack(jnp.zeros((packer.n_pad,), jnp.float32)), {}
    if use_kernels is None:
        use_kernels = True
    sharded = use_kernels and not _mesh_is_trivial(mesh)
    info: dict = {}
    tm = InflightMetrics(telemetry)
    if tm:
        tm.put("sync_n_workers", W)
        tm.put("sync_n_params", packer.n_params)
        tm.put("sync_n_pad", packer.n_pad)
        tm.put("sync_ingress_bytes", W * packer.n_pad * 4)
        tm.put("sync_egress_bytes",
               packer.n_params * 4
               if (out_shardings is not None and mesh is not None)
               else packer.n_pad * 4)

    def egress(out):
        with phase("unpack"):
            if out_shardings is None or mesh is None:
                return packer.unpack(reshard_out(out, mesh))
            return unpack_to_shardings(packer, out, out_shardings)

    def finish(out):
        if tm:
            info["telemetry"] = tm.tree()
        return egress(out), info

    with phase("pack"):
        buf = reshard_in(packer.pack(grads_w), mesh)  # [W, n_pad] fp32

    if aggregator.base.coordinatewise:
        mix_key = None if key is None else jax.random.split(key)[0]
        m = aggregator.mixer.matrix(mix_key, W)
        with phase("mix"):
            if not use_kernels:
                mixed = m @ buf
            else:
                mixed = (shard_kernels.mix_apply(m, buf, mesh, block_d=block_d)
                         if sharded else ops.mix_apply(m, buf, block_d=block_d))
        with phase("kernel"):
            if not use_kernels:
                out = aggregator.base.combine_leaf(mixed)
            elif aggregator.base.name == "cm":
                out = (shard_kernels.cm_aggregate(mixed, mesh, block_d=block_d)
                       if sharded else ops.cm_aggregate(mixed, block_d=block_d))
            elif aggregator.base.name == "tm":
                b = min(aggregator.base.n_trim, (mixed.shape[0] - 1) // 2)
                out = (shard_kernels.tm_aggregate(mixed, b, mesh, block_d=block_d)
                       if sharded else ops.tm_aggregate(mixed, b, block_d=block_d))
            elif sharded:  # any other combine_leaf is column-local too
                out = shard_kernels.coordinatewise_combine(
                    mixed, mesh, aggregator.base.combine_leaf)
            else:
                out = aggregator.base.combine_leaf(mixed)
        if tm:
            # probe math over the (possibly column-sharded) mixed buffer;
            # GSPMD inserts the column psums — telemetry-on programs only.
            tm.put("bucket_dispersion", lambda: _probes.bucket_dispersion(mixed))
            if aggregator.base.name == "cm":
                tm.put("cm_worker_dev", lambda: _probes.cm_worker_dev(
                    mixed, out, packer.n_params))
            elif aggregator.base.name == "tm":
                tm.put("tm_trim_frac", lambda: _probes.tm_trim_frac(
                    mixed, aggregator.base.n_trim, packer.n_params))
        return finish(out)

    if sharded and aggregator.base.name in ("rfa", "cclip"):
        # fused multi-device route: mix in vector space, then the sharded
        # Weiszfeld / fused-CCLIP composition — one local kernel pass plus
        # one [W]-sized psum per iteration instead of the [W, W] Gram
        # detour. Same math as the Gram route (weights = M^T c applied to
        # the buffer == c applied to the mixed buffer), fp32-tolerance
        # equal, asserted in tests/test_shard_engine.py. ACClip stays on
        # the Gram route (its adaptive tau needs the full norm vector).
        base = aggregator.base
        mix_key = None if key is None else jax.random.split(key)[0]
        m = aggregator.mixer.matrix(mix_key, W)
        with phase("mix"):
            mixed = shard_kernels.mix_apply(m, buf, mesh, block_d=block_d)
        with phase("kernel"):
            if base.name == "cclip":
                out = shard_kernels.cclip_aggregate(
                    mixed, base.tau, mesh, n_iters=base.n_iters, eps=base.eps,
                    block_d=block_d, with_stats=telemetry)
            else:
                out = shard_kernels.rfa_aggregate(
                    mixed, mesh, n_iters=base.n_iters, eps=base.eps,
                    block_d=block_d, with_stats=telemetry)
        if tm:
            out, stats = out
            tm.update(stats)
            tm.put("bucket_dispersion", lambda: _probes.bucket_dispersion(mixed))
        return finish(out)

    with phase("gram"):
        if not use_kernels:
            gram = buf @ buf.T
        elif sharded:
            gram = shard_kernels.gram(buf, mesh, block_d=block_d)
        else:
            gram = ops.gram(buf, block_d=block_d)
    with phase("coeff"):
        if tm:
            weights, stats = aggregator.worker_weights_and_stats_from_gram(
                gram, key=key)
            tm.update(stats)
        else:
            weights = aggregator.worker_weights_from_gram(gram, key=key)
    info["agg_weights"] = weights
    info["gram_diag_mean"] = jnp.mean(jnp.diagonal(gram))
    with phase("combine"):
        if not use_kernels:
            out = weights @ buf
        elif sharded:
            out = shard_kernels.mix_apply(weights[None, :], buf, mesh,
                                          block_d=block_d)[0]
        else:
            out = ops.mix_apply(weights[None, :], buf, block_d=block_d)[0]
    return finish(out)


def packed_aggregate(
    xs: jnp.ndarray,
    aggregator: RobustAggregator,
    key: Optional[jax.Array] = None,
    block_d: int = 2048,
    use_kernels: Optional[bool] = None,
    telemetry: bool = False,
    with_info: bool = False,
):
    """Packed engine on an already-stacked ``[W, d]`` matrix -> ``[d]``.

    The kernel-accelerated counterpart of ``RobustAggregator.__call__`` for
    callers that hold a flat stack (the cross-device FL server, benchmark
    harnesses): same mixing + rule, one pass over one padded buffer.
    ``with_info=True`` returns ``(out, info)`` — with ``telemetry=True``
    the info carries the device-resident metrics pytree."""
    out_tree, info = packed_robust_sync(
        [xs], aggregator, key=key, mesh=None, block_d=block_d,
        use_kernels=use_kernels, telemetry=telemetry,
    )
    if with_info:
        return out_tree[0], info
    return out_tree[0]
