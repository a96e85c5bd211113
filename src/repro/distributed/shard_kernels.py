"""shard_map partitioning for the Pallas aggregation kernels.

``pallas_call`` is opaque to GSPMD: inside a plain jit the partitioner
cannot split a kernel across devices, so the packed engine used to fall
back to jnp contractions on any non-trivial mesh and the kernels only ever
ran in the single-host simulation. This module closes that gap with
``shard_map``: every wrapper runs the kernel on the device-local COLUMN
slice of the packed ``[W, n_pad]`` buffer (the layout ``reshard_in``
already produces — parameter columns over ALL mesh axes, worker rows
replicated), and finishes with an explicit collective only where the math
reduces over the column axis:

  gram / residual_norms / the fused-CCLIP residual output
      column reductions  -> local kernel + ``psum`` over every mesh axis;
  mix_apply / cwise_median / cwise_trimmed_mean / combine_leaf / the
      fused-CCLIP center output
      column-local       -> no collective at all; outputs STAY
      column-sharded, which is exactly what the next phase (or the
      param-sharded egress in ``packing.py``) wants.

Local column counts need not be 128-aligned — each kernel wrapper pads its
own block internally — but they must be equal across devices, so inputs are
zero-padded up to a device-count multiple first (zero columns contribute 0
to every reduction and are sliced off sharded outputs).

Numerics: the per-device block-dot order differs from the single-device
kernel schedule, so results match the trivial-mesh kernel path (and the
GSPMD jnp path) to fp32 tolerance, not bit-for-bit. Asserted against both
references in tests/test_shard_engine.py on a forced 8-device host
platform.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops


def _axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def _flat(mesh):
    ax = _axes(mesh)
    return ax if len(ax) > 1 else ax[0]


def col_spec(mesh) -> P:
    """``[W, n]`` with the column axis over ALL mesh axes (reshard_in's layout)."""
    return P(None, _flat(mesh))


def vec_spec(mesh) -> P:
    """``[n]`` laid over ALL mesh axes."""
    return P(_flat(mesh))


def _pad_cols(x: jnp.ndarray, mesh) -> Tuple[jnp.ndarray, int]:
    """Zero-pad the last axis up to a device-count multiple (shard_map needs
    equal per-device blocks). Returns ``(padded, original_n)``."""
    n_dev = int(mesh.devices.size)
    n = x.shape[-1]
    n_up = -(-n // n_dev) * n_dev
    if n_up == n:
        return x, n
    pad = [(0, 0)] * (x.ndim - 1) + [(0, n_up - n)]
    return jnp.pad(x, pad), n


# ------------------------------------------------------------------ kernels
def gram(buf: jnp.ndarray, mesh, *, block_d: int = 2048) -> jnp.ndarray:
    """Sharded stats phase: local ``[W, n/n_dev]`` Gram + psum -> ``[W, W]``."""
    ax = _axes(mesh)
    buf, _ = _pad_cols(buf, mesh)
    body = lambda b: jax.lax.psum(ops.gram(b, block_d=block_d), ax)
    return jax.shard_map(body, mesh=mesh, in_specs=(col_spec(mesh),),
                     out_specs=P(), check_vma=False)(buf)


def mix_apply(mix: jnp.ndarray, buf: jnp.ndarray, mesh, *,
              block_d: int = 2048) -> jnp.ndarray:
    """Sharded mixing/combine: the tiny ``[m, W]`` operator is replicated and
    each device mixes its own columns — no collective; output stays
    column-sharded."""
    buf, n = _pad_cols(buf, mesh)
    body = lambda m, b: ops.mix_apply(m, b, block_d=block_d)
    out = jax.shard_map(body, mesh=mesh, in_specs=(P(None, None), col_spec(mesh)),
                    out_specs=col_spec(mesh), check_vma=False)(mix, buf)
    return out[:, :n] if n != out.shape[1] else out


def cm_aggregate(buf: jnp.ndarray, mesh, *, block_d: int = 4096) -> jnp.ndarray:
    """Sharded coordinate-wise median: column-local selection network per
    device; output is the column-sharded ``[n]`` aggregate."""
    buf, n = _pad_cols(buf, mesh)
    body = lambda b: ops.cm_aggregate(b, block_d=block_d)
    out = jax.shard_map(body, mesh=mesh, in_specs=(col_spec(mesh),),
                    out_specs=vec_spec(mesh), check_vma=False)(buf)
    return out[:n] if n != out.shape[0] else out


def tm_aggregate(buf: jnp.ndarray, n_trim: int, mesh, *,
                 block_d: int = 4096) -> jnp.ndarray:
    """Sharded coordinate-wise trimmed mean: column-local selection network
    per device; output is the column-sharded ``[n]`` aggregate."""
    buf, n = _pad_cols(buf, mesh)
    body = lambda b: ops.tm_aggregate(b, n_trim, block_d=block_d)
    out = jax.shard_map(body, mesh=mesh, in_specs=(col_spec(mesh),),
                    out_specs=vec_spec(mesh), check_vma=False)(buf)
    return out[:n] if n != out.shape[0] else out


def coordinatewise_combine(buf: jnp.ndarray, mesh,
                           combine_fn: Callable) -> jnp.ndarray:
    """Any column-local ``[W, n] -> [n]`` reduction (an aggregator's
    ``combine_leaf`` — trimmed mean etc.) run per column shard."""
    buf, n = _pad_cols(buf, mesh)
    out = jax.shard_map(combine_fn, mesh=mesh, in_specs=(col_spec(mesh),),
                    out_specs=vec_spec(mesh), check_vma=False)(buf)
    return out[:n] if n != out.shape[0] else out


def residual_norms(buf: jnp.ndarray, coeffs: Optional[jnp.ndarray] = None, *,
                   center: Optional[jnp.ndarray] = None, mesh,
                   block_d: int = 2048) -> jnp.ndarray:
    """Sharded Weiszfeld/CCLIP norms phase: local fused pass + psum -> [W].
    The center is given either as ``coeffs`` [W] (replicated; the candidate
    is formed blockwise in VMEM) or as an explicit ``center`` [d] row
    (column-sharded alongside ``buf``)."""
    if (coeffs is None) == (center is None):
        raise ValueError("provide exactly one of coeffs / center")
    ax = _axes(mesh)
    buf, _ = _pad_cols(buf, mesh)
    if coeffs is not None:
        body = lambda b, c: jax.lax.psum(ops.norms(b, c, block_d=block_d), ax)
        return jax.shard_map(body, mesh=mesh, in_specs=(col_spec(mesh), P(None)),
                         out_specs=P(), check_vma=False)(buf, coeffs)
    center, _ = _pad_cols(center, mesh)
    body = lambda b, v: jax.lax.psum(
        ops.norms(b, center=v, block_d=block_d), ax)
    return jax.shard_map(body, mesh=mesh,
                     in_specs=(col_spec(mesh), vec_spec(mesh)),
                     out_specs=P(), check_vma=False)(buf, center)


def cclip_fused_iter(buf: jnp.ndarray, v: jnp.ndarray, lam: jnp.ndarray,
                     mesh, *, block_d: int = 2048
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sharded fused CCLIP iteration: the center update is column-local (the
    new center stays column-sharded, one HBM pass over the local slice); the
    next-iteration residuals finish with a psum."""
    ax = _axes(mesh)
    buf, n = _pad_cols(buf, mesh)
    v, _ = _pad_cols(v, mesh)

    def body(b, vv, ll):
        v_new, r2 = ops.cclip_iter(b, vv, ll, block_d=block_d)
        return v_new, jax.lax.psum(r2, ax)

    v_new, r2 = jax.shard_map(
        body, mesh=mesh,
        in_specs=(col_spec(mesh), vec_spec(mesh), P(None)),
        out_specs=(vec_spec(mesh), P()), check_vma=False)(buf, v, lam)
    return (v_new[:n] if n != v_new.shape[0] else v_new), r2


# ------------------------------------------------------------- compositions
def rfa_aggregate(xs: jnp.ndarray, mesh, *, n_iters: int = 8,
                  eps: float = 1e-6, block_d: int = 2048,
                  with_stats: bool = False):
    """Mesh-partitioned counterpart of ``ops.rfa_aggregate``: smoothed
    Weiszfeld with one sharded norms pass (+psum) per iteration.

    ``with_stats=True`` additionally returns the telemetry stats dict (the
    per-iteration residual norms the loop computes anyway, exported as scan
    ys). With the default False, the traced program is the seed program —
    no extra outputs, no extra collectives."""
    W = xs.shape[0]

    def body(c, _):
        r2 = residual_norms(xs, c, mesh=mesh, block_d=block_d)
        w = 1.0 / jnp.sqrt(r2 + eps**2)
        return w / jnp.sum(w), (r2 if with_stats else None)

    c0 = jnp.full((W,), 1.0 / W, jnp.float32)
    c, r2_seq = jax.lax.scan(body, c0, None, length=n_iters)
    out = mix_apply(c[None, :], xs, mesh, block_d=block_d)[0]
    if not with_stats:
        return out
    r_seq = jnp.sqrt(r2_seq + eps**2)
    stats = {
        "rfa_resid_norms": r_seq,                  # [T, W]
        "rfa_residual": jnp.sum(r_seq, axis=1),    # [T]
        "rfa_iters": n_iters,
    }
    return out, stats


def cclip_aggregate(xs: jnp.ndarray, tau: float, mesh, *, n_iters: int = 3,
                    eps: float = 1e-12, block_d: int = 2048,
                    with_stats: bool = False):
    """Mesh-partitioned counterpart of ``ops.cclip_aggregate``: one fused
    sharded pass per iteration (combine column-local, norms psum).

    ``with_stats=True`` additionally returns the telemetry stats dict (clip
    weights per iteration as scan ys). False traces the seed program."""
    W = xs.shape[0]
    v = mix_apply(jnp.full((1, W), 1.0 / W, jnp.float32), xs, mesh,
                  block_d=block_d)[0]
    r2 = residual_norms(xs, center=v, mesh=mesh, block_d=block_d)

    def body(carry, _):
        v, r2 = carry
        lam = jnp.minimum(1.0, tau / jnp.sqrt(r2 + eps))
        new_carry = cclip_fused_iter(xs, v, lam, mesh, block_d=block_d)
        return new_carry, (lam if with_stats else None)

    (v, _), lam_seq = jax.lax.scan(body, (v, r2), None, length=n_iters)
    if not with_stats:
        return v
    lam32 = lam_seq.astype(jnp.float32)
    stats = {
        "cclip_lam": lam32,                        # [T, W]
        "cclip_clip_frac": jnp.mean(
            (lam32 < 1.0).astype(jnp.float32), axis=1),
        "cclip_tau": jnp.full((n_iters,), tau, jnp.float32),
    }
    return v, stats
