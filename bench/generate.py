"""The one generator of inputs, driven by a traffic file's parameters.

Everything is drawn on the device from a key made from ``--seed``, in one
jitted call per kind of input, so the same seed gives the same inputs.

``token_streams``: per-worker token sequences with a noisy affine bigram
law ``next = (a_w * tok + b_w) mod V`` per worker (non-iid "dialects"),
copied from the program's ``data.synthetic.make_token_stream`` so that the
benchmark's traffic does not move when the program's helper does.

``alie_messages``: the sync cells' worker messages. Honest rows are unit
normal noise around a worker-dependent mean (``linspace(-1, 1)``, the
non-iid case); the Byzantine rows all send ``mean - z * std`` of the honest
rows, coordinate by coordinate, as ALIE ("a little is enough", Baruch et
al. 2019) builds them, with z from the normal quantile for (n, f).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import jax
import jax.numpy as jnp


def seed_key(seed: int, impl: str = "threefry2x32"):
    """A key from a seed of any size: the low 32 bits seed it, the rest is
    folded in (a plain PRNGKey drops bits above 32)."""
    key = jax.random.key(seed & 0xFFFFFFFF, impl=impl)
    return jax.random.fold_in(key, seed >> 32)


def alie_z(n: int, f: int) -> float:
    """Baruch et al. 2019: the largest z with Phi(z) < (n - f - s) / (n - f),
    s = floor(n / 2 + 1) - f."""
    s = math.floor(n / 2 + 1) - f
    return NormalDist().inv_cdf((n - f - s) / (n - f))


def alie_messages(key, shapes, workers: int, byzantine: int):
    """Tree of [workers, *shape] float32 messages; rows ``workers -
    byzantine`` and up are the Byzantine ones."""
    honest = workers - byzantine
    means = jnp.linspace(-1.0, 1.0, honest)
    z = alie_z(workers, byzantine)
    leaves, treedef = jax.tree_util.tree_flatten(shapes)

    def one(k, shape):
        x = jax.random.normal(k, (honest,) + tuple(shape), jnp.float32)
        x = x + means.reshape((honest,) + (1,) * len(shape))
        mu, sd = jnp.mean(x, axis=0), jnp.std(x, axis=0)
        bad = jnp.broadcast_to(mu - z * sd, (byzantine,) + tuple(shape))
        return jnp.concatenate([x, bad], axis=0)

    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [one(k, s.shape) for k, s in zip(keys, leaves)])


def token_streams(key, workers: int, n_seqs: int, seq_len: int, vocab: int,
                  noise_p: float) -> jnp.ndarray:
    """[workers, n_seqs, seq_len + 1] int32 (inputs and next-token labels)."""
    k_ab, k_init, k_noise, k_unif = jax.random.split(key, 4)
    a = jax.random.randint(k_ab, (workers,), 1, 97) * 2 + 1
    b = jax.random.randint(jax.random.fold_in(k_ab, 1), (workers,), 0, vocab)
    shape = (workers, n_seqs)
    tok0 = jax.random.randint(k_init, shape, 0, vocab)
    flips = jax.random.bernoulli(k_noise, noise_p, shape + (seq_len,))
    unif = jax.random.randint(k_unif, shape + (seq_len,), 0, vocab)

    def step(tok, inputs):
        flip, u = inputs
        nxt = jnp.where(flip, u, jnp.mod(a[:, None] * tok + b[:, None], vocab))
        return nxt, tok

    _, toks = jax.lax.scan(step, tok0, (jnp.moveaxis(flips, -1, 0),
                                        jnp.moveaxis(unif, -1, 0)))
    toks = jnp.moveaxis(toks, 0, -1)
    last = jnp.mod(a[:, None] * toks[..., -1] + b[:, None], vocab)
    return jnp.concatenate([toks, last[..., None]], axis=-1).astype(jnp.int32)
