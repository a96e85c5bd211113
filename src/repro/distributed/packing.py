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
offsets, computed once per tree structure and cached (``packer_for``).

LAYOUT. A leaf of shape ``[*lead, C]`` is laid out in its segment as its
``[R, C']`` view, ``R = prod(lead)``: row-major, with the minor dim C
rounded up to ``C' = lane_width(C)``, a multiple of 128 lanes, and zeros in
the extra lanes. Every row of such a leaf then starts on a lane-tile
boundary, so flattening it moves whole 128-lane tiles to whole tiles: one
transposing copy on a TPU, where an unaligned C (``in_proj``'s 3352) made
XLA relay the leaf through a flat 1-D copy and a loop over the worker rows.
A minor dim narrower than one lane tile (a conv kernel's 4, a per-head 24)
keeps its dense flattening: padding it would multiply the leaf by up to
128/C, and such leaves are small. Each segment is then padded up to a
``block_d`` multiple. That per-leaf alignment is what makes the packed
engine BIT-IDENTICAL to the per-leaf oracle, which flattens each leaf by
the same ``to_lanes`` view: the Gram kernel (kernels/pairwise_gram.py)
accumulates fixed ``[W, block_d]`` block dots in column order, so one call
over the packed buffer performs the exact same sequence of fp32 operations
as the oracle's chain of per-leaf calls (seeded via the kernel's ``acc``
input). Mixing and combine reduce over the (tiny, zero-padded) worker axis
per column, which is insensitive to column blocking. Asserted in
tests/test_packing.py. ``unpack*`` drop the extra lanes before restoring
each leaf's shape.

ROWS. Where the kernels are called directly (a trivial mesh), ``pack``
writes ``max(8, round_up(W, 8))`` rows, the sublane multiple the kernels'
blocks need, so the buffer is born in the shape ``pairwise_gram`` and
``bucket_mix`` read and their wrappers neither pad nor copy it. The extra
rows are zero and reach only those two kernels, which contract over the
worker axis: the sync slices the Gram back to ``[W, W]`` and gives the
padded rows zero columns of the mixing matrix and zero combine weights.
They never reach the order-statistic kernels (``cwise_median``,
``cwise_trimmed_mean``), whose padding sentinel is ``+inf``, not 0: those
read only the mixed ``[m, n_pad]`` rows. On a multi-device mesh ``pack``
keeps W rows: ``reshard_in``'s all-to-all carries the messages and nothing
else, and each device's kernel pads its own local block.

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
from repro.kernels import ops, pack_rows
from repro.telemetry import InflightMetrics, phase
from repro.telemetry import probes as _probes


LANE = 128  # a TPU vreg's lane count: the minor tile of every fp32 layout


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def kernel_rows(W: int) -> int:
    """Rows of a buffer the kernels read without padding it: W rounded up
    to the 8-row sublane multiple."""
    return max(8, _round_up(W, 8))


def lane_width(c: int) -> int:
    """Columns a leaf row of minor dim ``c`` takes in the packed buffer:
    ``c`` rounded up to whole lane tiles, or ``c`` itself when it is
    narrower than one tile (module docstring, LAYOUT)."""
    return _round_up(c, LANE) if c > LANE else c


def to_lanes(x: jnp.ndarray, rows: Optional[int] = None) -> jnp.ndarray:
    """Stacked leaf ``[W, *shape]`` -> its ``[rows, R * C']`` view: minor
    dim zero-padded to ``lane_width``, worker rows zero-padded to ``rows``
    (default W). Keeps the dtype."""
    W = x.shape[0]
    c = x.shape[-1] if x.ndim > 1 else 1
    x = x.reshape(W, -1, c)
    rows = W if rows is None else rows
    if rows != W or lane_width(c) != c:
        x = jnp.pad(x, ((0, rows - W), (0, 0), (0, lane_width(c) - c)))
    return x.reshape(rows, -1)


def from_lanes(y: jnp.ndarray, shape: tuple) -> jnp.ndarray:
    """Inverse of ``to_lanes`` on the trailing axis: ``[..., R * C']`` ->
    ``[..., *shape]``, extra lanes dropped."""
    c = shape[-1] if shape else 1
    lead = y.shape[:-1]
    if lane_width(c) != c:
        y = y.reshape(lead + (-1, lane_width(c)))[..., :c]
    return y.reshape(lead + tuple(shape))


class GradPacker:
    """Flattens a per-worker gradient pytree (leaves ``[W, ...]``) into one
    padded ``[rows, n_pad]`` fp32 buffer of lane-aligned, ``block_d``-
    aligned leaf segments, and back (module docstring, LAYOUT and ROWS).
    Layout is static per tree structure; build instances via
    ``packer_for`` to get caching."""

    def __init__(self, treedef, leaf_shapes: Tuple[tuple, ...],
                 leaf_dtypes: tuple, block_d: int = 2048):
        if block_d % LANE:
            raise ValueError(f"block_d must be a multiple of {LANE}, got {block_d}")
        self.treedef = treedef
        self.leaf_shapes = tuple(tuple(s) for s in leaf_shapes)  # sans worker axis
        self.leaf_dtypes = tuple(jnp.dtype(d) for d in leaf_dtypes)
        self.block_d = int(block_d)
        self.sizes = tuple(math.prod(s) for s in self.leaf_shapes)
        # columns of each leaf's lane-aligned [R, C'] view
        self.spans = tuple(
            z // s[-1] * lane_width(s[-1]) if z and s else z
            for z, s in zip(self.sizes, self.leaf_shapes))
        # each segment is padded to a block_d multiple so kernel blocks
        # never straddle leaves (the bit-exactness alignment)
        self.padded = tuple(_round_up(z, block_d) for z in self.spans)
        self.offsets = tuple(
            sum(self.padded[:i]) for i in range(len(self.padded))
        )
        self.n_params = sum(self.sizes)
        self.lane_pad_cols = sum(self.spans) - self.n_params
        self.n_pad = sum(self.padded)

    # ------------------------------------------------------------------ pack
    def pack(self, grads_w: Any, for_kernels: bool = False) -> jnp.ndarray:
        """Stacked tree (leaves ``[W, ...]``) -> packed ``[rows, n_pad]`` fp32.

        ``for_kernels`` says the sync kernels will read the buffer directly
        (a trivial mesh): it then has ``kernel_rows(W)`` rows, and the
        ``pack_rows`` kernel writes each leaf of whole-tile rows in one pass
        (module docstring, ROWS). Otherwise it has W rows. Every other leaf
        is written as one padded piece by XLA. Each segment is written once,
        padding included: there is no zero-filled buffer to update."""
        leaves = jax.tree_util.tree_leaves(grads_w)
        W = leaves[0].shape[0]
        rows = kernel_rows(W) if for_kernels else W
        buf, pieces = None, []
        for leaf, shape, size, span, seg, off in zip(
                leaves, self.leaf_shapes, self.sizes, self.spans, self.padded,
                self.offsets):
            if size == 0:
                continue
            x = leaf.astype(jnp.float32)
            C = shape[-1] if shape else 1
            if for_kernels and pack_rows.supports(size // C, C):
                buf = pack_rows.pack_rows(
                    x.reshape(W, -1, C), (rows, self.n_pad) if buf is None else buf,
                    off=off, seg=seg)
            else:
                x = to_lanes(x, rows)
                pieces.append((off, x if seg == span else
                               jnp.pad(x, ((0, 0), (0, seg - span)))))
        if buf is None:
            return (jnp.concatenate([p for _, p in pieces], axis=1) if pieces
                    else jnp.zeros((rows, self.n_pad), jnp.float32))
        for off, piece in pieces:
            buf = jax.lax.dynamic_update_slice(buf, piece, (0, off))
        return buf

    # ---------------------------------------------------------------- unpack
    def _leaves(self, cols) -> Any:
        """``cols(offset, span)`` -> that segment's ``[..., span]`` columns;
        returns the tree of leaves in their shapes and dtypes."""
        leaves = [
            from_lanes(cols(off, span), shape).astype(dtype)
            for off, span, shape, dtype in zip(
                self.offsets, self.spans, self.leaf_shapes, self.leaf_dtypes)
        ]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def unpack(self, vec: jnp.ndarray) -> Any:
        """Packed row ``[n_pad]`` -> gradient tree (original shapes/dtypes)."""
        return self._leaves(lambda off, span: vec[off:off + span])

    def unpack_stacked(self, buf: jnp.ndarray) -> Any:
        """Packed stack ``[k, n_pad]`` -> tree with the leading axis kept."""
        return self._leaves(lambda off, span: buf[:, off:off + span])

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
            from_lanes(vec[off:off + span], shape).astype(dtype), sh)
        for off, span, shape, dtype, sh in zip(
            packer.offsets, packer.spans, packer.leaf_shapes,
            packer.leaf_dtypes, shardings)
    ]
    return jax.tree_util.tree_unflatten(packer.treedef, leaves)


def _mesh_is_trivial(mesh) -> bool:
    return mesh is None or mesh.devices.size == 1


def _widen(op: jnp.ndarray, rows: int) -> jnp.ndarray:
    """A ``[k, W]`` operator over the worker axis, zero-padded to ``[k, rows]``
    so that the buffer's padded rows take no weight."""
    W = op.shape[-1]
    return op if rows == W else jnp.pad(op, ((0, 0), (0, rows - W)))


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
    # rows padded at birth where the kernels run unsharded (module
    # docstring, ROWS); the worker axis of every operator is widened to match
    direct = use_kernels and not sharded
    rows = kernel_rows(W) if direct else W
    info: dict = {}
    tm = InflightMetrics(telemetry)
    if tm:
        tm.put("sync_n_workers", W)
        tm.put("sync_n_params", packer.n_params)
        tm.put("sync_n_pad", packer.n_pad)
        tm.put("sync_lane_pad_cols", packer.lane_pad_cols)
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
        buf = reshard_in(packer.pack(grads_w, for_kernels=direct), mesh)

    if aggregator.base.coordinatewise:
        mix_key = None if key is None else jax.random.split(key)[0]
        m = _widen(aggregator.mixer.matrix(mix_key, W), rows)
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
            gram = ops.gram(buf, block_d=block_d)[:W, :W]
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
            out = ops.mix_apply(_widen(weights[None, :], rows), buf,
                                block_d=block_d)[0]
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
