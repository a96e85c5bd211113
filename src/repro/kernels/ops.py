"""jit'd public wrappers over the Pallas kernels.

Interpret mode is chosen in one place, ``_interp``: every kernel's
``interpret=None`` default resolves through it to Mosaic on a TPU backend
and to the Pallas interpreter on any other (how the CPU tests run the
kernel bodies). An explicit ``interpret=False`` always asks for Mosaic.

The composed aggregators here are the kernel-accelerated counterparts of
``repro.core.aggregators`` (oracles in ``ref.py``; equivalence is asserted
in tests/test_kernels.py):

  gram(xs, acc=...)           stats phase for Krum / RFA / CCLIP
  cm_aggregate(xs)            full coordinate-wise median
  tm_aggregate(xs, n_trim)    coordinate-wise trimmed mean (sorted band)
  mix_apply(M, xs)            bucketing / resampling application
  norms(xs, c | center=v)     residual sq-norms (Weiszfeld / CCLIP inner loop)
  cclip_iter(xs, v, lam)      one fused CCLIP iteration (combine + next norms)
  rfa_aggregate(xs)           smoothed Weiszfeld via fused residual-norm passes
  cclip_aggregate(xs, tau)    centered clipping, ONE fused HBM pass/iteration

Everything here is SINGLE-DEVICE: inside a jit, GSPMD cannot partition a
``pallas_call``, so on a multi-device mesh these wrappers would run the
whole array on every device. The mesh-partitioned counterparts (each device
running the kernel on its local column slice, with explicit psums for the
reducing phases) live in ``repro.distributed.shard_kernels``.

``cclip_aggregate`` runs each iteration through ``cclip_fused_iter``
(combine + next-iteration norms in one streaming pass); the pre-fusion
two-kernel schedule is kept as ``cclip_aggregate_unfused`` — it is the
benchmark baseline in benchmarks/agg_microbench.py and documents what the
fusion saves (a norms pass over a ``[W+1, d]`` pseudo-row stack built by a
full `jnp.concatenate` copy, plus a separate combine pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bucket_mix import bucket_mix
from repro.kernels.cclip_combine import cclip_combine
from repro.kernels.cclip_fused import cclip_fused_iter
from repro.kernels.cwise_median import cwise_median
from repro.kernels.pairwise_gram import pairwise_gram
from repro.kernels.trimmed_mean import cwise_trimmed_mean
from repro.kernels.weiszfeld_norms import residual_norms


def _interp(interpret: bool | None = None) -> bool:
    """The interpret-mode rule: an explicit bool is kept; ``None`` means
    interpret on every backend but the TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def gram(xs: jnp.ndarray, acc: jnp.ndarray | None = None, *,
         block_d: int = 2048, full_blocks: bool = False) -> jnp.ndarray:
    return pairwise_gram(xs, acc, block_d=block_d, full_blocks=full_blocks)


def cm_aggregate(xs: jnp.ndarray, *, block_d: int = 4096) -> jnp.ndarray:
    return cwise_median(xs, block_d=block_d)


def tm_aggregate(xs: jnp.ndarray, n_trim: int, *, block_d: int = 4096) -> jnp.ndarray:
    return cwise_trimmed_mean(xs, n_trim, block_d=block_d)


def mix_apply(mix: jnp.ndarray, xs: jnp.ndarray, *, block_d: int = 2048) -> jnp.ndarray:
    return bucket_mix(mix, xs, block_d=block_d)


def norms(xs: jnp.ndarray, coeffs: jnp.ndarray | None = None, *,
          center: jnp.ndarray | None = None, block_d: int = 2048) -> jnp.ndarray:
    """Residual sq-norms ``||x_i - v||^2`` with v as coeffs or explicit row."""
    return residual_norms(xs, coeffs, center=center, block_d=block_d)


def cclip_iter(xs: jnp.ndarray, v: jnp.ndarray, lam: jnp.ndarray, *,
               block_d: int = 2048):
    """One fused CCLIP iteration -> ``(v', ||x_i - v'||^2)``."""
    return cclip_fused_iter(xs, v, lam, block_d=block_d)


@functools.partial(jax.jit, static_argnames=("n_iters", "block_d"))
def rfa_aggregate(xs: jnp.ndarray, *, n_iters: int = 8, eps: float = 1e-6,
                  block_d: int = 2048) -> jnp.ndarray:
    """Geometric median of worker rows via kernel-fused Weiszfeld."""
    W = xs.shape[0]

    def body(c, _):
        r2 = residual_norms(xs, c, block_d=block_d)
        w = 1.0 / jnp.sqrt(r2 + eps**2)
        return w / jnp.sum(w), None

    c0 = jnp.full((W,), 1.0 / W, jnp.float32)
    c, _ = jax.lax.scan(body, c0, None, length=n_iters)
    return mix_apply(c[None, :], xs, block_d=block_d)[0]


@functools.partial(jax.jit, static_argnames=("n_iters", "block_d"))
def cclip_aggregate(xs: jnp.ndarray, tau: float, *, n_iters: int = 3,
                    eps: float = 1e-12, block_d: int = 2048) -> jnp.ndarray:
    """Centered clipping: ONE fused (combine + next-norms) pass per iteration.

    The fused kernel returns ``v'`` together with ``||x_i - v'||^2``, so the
    residuals each iteration needs were already computed while the previous
    update streamed by — only the initial center costs a dedicated norms
    pass (with an explicit center row; no pseudo-row concat).
    """
    W = xs.shape[0]
    v = mix_apply(jnp.full((1, W), 1.0 / W, jnp.float32), xs, block_d=block_d)[0]
    r2 = residual_norms(xs, center=v, block_d=block_d)

    def body(carry, _):
        v, r2 = carry
        lam = jnp.minimum(1.0, tau / jnp.sqrt(r2 + eps))
        return cclip_fused_iter(xs, v, lam, block_d=block_d), None

    (v, _), _ = jax.lax.scan(body, (v, r2), None, length=n_iters)
    return v


@functools.partial(jax.jit, static_argnames=("n_iters", "block_d"))
def cclip_aggregate_unfused(xs: jnp.ndarray, tau: float, *, n_iters: int = 3,
                            eps: float = 1e-12, block_d: int = 2048) -> jnp.ndarray:
    """Pre-fusion CCLIP schedule: norms pass + combine pass per iteration,
    with the center appended to the stack as a pseudo-row (a full stack
    copy). Kept as the microbenchmark baseline for ``cclip_aggregate``."""
    W = xs.shape[0]
    v = mix_apply(jnp.full((1, W), 1.0 / W, jnp.float32), xs, block_d=block_d)[0]

    def body(v, _):
        diffs2 = residual_norms(
            jnp.concatenate([xs.astype(jnp.float32), v[None, :]], axis=0),
            jnp.zeros((W + 1,), jnp.float32).at[W].set(1.0),
            block_d=block_d,
        )[:W]
        lam = jnp.minimum(1.0, tau / jnp.sqrt(diffs2 + eps))
        v_new = cclip_combine(xs, v, lam, block_d=block_d)
        return v_new, None

    v, _ = jax.lax.scan(body, v, None, length=n_iters)
    return v
