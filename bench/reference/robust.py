"""Plain bucketing + robust rules on stacked worker vectors, for the sync
cells and the train cells' aggregation.

Bucketing (Karimireddy, He and Jaggi, ICLR 2022, Algorithm 1): permute the
W workers by ``jax.random.permutation(key)`` and average consecutive groups
of s into ceil(W / s) bucket rows. RFA is the smoothed Weiszfeld iteration
(Pillutla et al.) started from the mean of the bucket rows; CM is the
coordinatewise median of the bucket rows (the midpoint of the two middle
values for an even count).

``aggregate`` is the reference: float64 on the host, the W x W Gram matrix
and the weighted sum taken in column blocks through BLAS. ``aggregate_jnp``
is the same algorithm in float32 on the default device with every
contraction done as ``dot_high``: three bf16 passes, the step below the
``highest`` the sync states, written out so that it rounds the same on any
backend. The calibration and the tests run it as the control. Nothing here
imports the program.
"""

from __future__ import annotations

import functools
import math
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 20


def bucket_matrix(key, workers: int, s: int) -> np.ndarray:
    """[ceil(W/s), W] float64: bucket b averages permuted slots b*s..b*s+s-1."""
    perm = np.asarray(jax.random.permutation(key, workers)) if workers > 1 \
        else np.zeros(1, int)
    m = math.ceil(workers / s)
    mat = np.zeros((m, workers))
    for slot, w in enumerate(perm):
        mat[slot // s, w] = 1.0
    return mat / mat.sum(axis=1, keepdims=True)


def mix_key(key):
    """The key the bucketing permutation is drawn from, for a call's key."""
    return jax.random.split(key)[0]


def rfa_weights(sq_dist: np.ndarray, iters: int, eps: float) -> np.ndarray:
    """Smoothed Weiszfeld from the rows' pairwise squared distances; returns
    the convex weights of the last iterate."""
    m = sq_dist.shape[0]
    c = np.full(m, 1.0 / m)
    for _ in range(iters):
        # ||sum_j c_j y_j - y_i||^2 = (D c)_i - c^T D c / 2, for sum(c) = 1
        r2 = np.maximum(sq_dist @ c - 0.5 * c @ sq_dist @ c, 0.0)
        w = 1.0 / np.sqrt(r2 + eps ** 2)
        c = w / w.sum()
    return c


def _column_blocks(xs: np.ndarray, fn) -> list:
    """``fn(block_as_float64, columns)`` over column blocks, on threads (the
    float64 casts and BLAS calls release the interpreter lock)."""
    cols = [slice(j, min(j + BLOCK, xs.shape[1])) for j in range(0, xs.shape[1], BLOCK)]
    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        return list(pool.map(lambda c: fn(xs[:, c].astype(np.float64), c), cols))


def gram64(xs: np.ndarray) -> np.ndarray:
    """X X^T in float64."""
    return sum(_column_blocks(xs, lambda blk, _: blk @ blk.T))


def _sq_dist(gram: np.ndarray) -> np.ndarray:
    d = np.diagonal(gram)
    return np.maximum(d[:, None] + d[None, :] - 2.0 * gram, 0.0)


def aggregate(xs: np.ndarray, keys: list, rule: dict) -> np.ndarray:
    """Reference aggregates of the rows of ``xs`` ([W, n] on the host), one
    per key -> [len(keys), n] float64."""
    mats = [bucket_matrix(mix_key(k), xs.shape[0], rule["s"]) for k in keys]
    out = np.empty((len(keys), xs.shape[1]))
    if rule["name"] == "rfa":
        g = gram64(xs)
        w = np.stack([M.T @ rfa_weights(_sq_dist(M @ g @ M.T), rule["iters"], rule["eps"])
                      for M in mats])

        def combine(blk, c):
            out[:, c] = w @ blk
    elif rule["name"] == "cm":
        mid = (mats[0].shape[0] - 1) // 2
        odd = mats[0].shape[0] % 2 == 1

        def combine(blk, c):
            for i, M in enumerate(mats):
                y = np.partition(M @ blk, [mid, mid + 1 - odd], axis=0)
                out[i, c] = y[mid] if odd else 0.5 * (y[mid] + y[mid + 1])
    else:
        raise ValueError(f"no reference for rule {rule['name']!r}")
    _column_blocks(xs, combine)
    return out


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def dot_high(a, b):
    """float32 ``a @ b`` as three bf16 passes with float32 accumulation
    (hi*hi + hi*lo + lo*hi), what ``Precision.HIGH`` asks of a TPU. The
    parts are rounded with ``reduce_precision``, which no backend elides,
    and multiplied exactly, so every backend rounds alike."""
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)

    def d(x, y):
        return jnp.dot(x, y, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    return d(ah, bh) + d(ah, bl) + d(al, bh)


@functools.partial(jax.jit, static_argnames=("rule_name", "iters", "eps"))
def _control(xs, mix, rule_name: str, iters: int, eps: float):
    if rule_name == "cm":
        return jnp.median(dot_high(mix, xs), axis=0)
    gy = dot_high(dot_high(mix, dot_high(xs, xs.T)), mix.T)
    diag = jnp.diagonal(gy)
    dist = jnp.maximum(diag[:, None] + diag[None, :] - 2.0 * gy, 0.0)
    c = jnp.full((mix.shape[0],), 1.0 / mix.shape[0], jnp.float32)
    for _ in range(iters):
        dc = dot_high(dist, c[:, None])[:, 0]
        r2 = jnp.maximum(dc - 0.5 * dot_high(c[None, :], dc[:, None])[0, 0], 0.0)
        w = 1.0 / jnp.sqrt(r2 + eps ** 2)
        c = w / jnp.sum(w)
    return dot_high(dot_high(c[None, :], mix), xs)[0]


def aggregate_jnp(xs, key, rule: dict) -> jnp.ndarray:
    """The reference's algorithm in float32 on the device, every contraction
    (the Gram, the bucketing, the Weiszfeld steps, the combine) as
    ``dot_high``: the control."""
    mix = jnp.asarray(bucket_matrix(mix_key(key), xs.shape[0], rule["s"]), jnp.float32)
    return _control(xs, mix, rule["name"], rule.get("iters", 0), rule.get("eps", 0.0))
