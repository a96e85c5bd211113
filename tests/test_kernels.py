"""Per-kernel allclose vs the pure-jnp oracles (ref.py), interpret mode.

Sweeps worker counts, parameter dims (aligned and ragged), block sizes and
dtypes per the assignment's kernel-validation requirement.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    bucket_mix,
    cclip_combine,
    cwise_median,
    cwise_trimmed_mean,
    pairwise_gram,
    residual_norms,
)
from repro.kernels import ops, ref

SHAPES = [(4, 128), (10, 1000), (25, 4097), (53, 257), (64, 8192), (7, 64)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _xs(shape, dtype, seed=0):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32) * 3).astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pairwise_gram(shape, dtype):
    xs = _xs(shape, dtype)
    tol = dict(rtol=1e-5, atol=1e-3) if dtype == jnp.float32 else dict(rtol=3e-2, atol=1.0)
    np.testing.assert_allclose(pairwise_gram(xs), ref.pairwise_gram(xs), **tol)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_cwise_median(shape, dtype):
    xs = _xs(shape, dtype)
    np.testing.assert_allclose(
        cwise_median(xs), ref.cwise_median(xs), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_cwise_trimmed_mean(shape, dtype):
    W, d = shape
    xs = _xs(shape, dtype)
    for n_trim in sorted({0, 1, (W - 1) // 2}):
        np.testing.assert_allclose(
            cwise_trimmed_mean(xs, n_trim), ref.cwise_trimmed_mean(xs, n_trim),
            rtol=1e-6, atol=1e-6,
        )


def test_cwise_trimmed_mean_rejects_empty_band():
    xs = _xs((4, 128), jnp.float32)
    with pytest.raises(ValueError):
        cwise_trimmed_mean(xs, 2)  # band [2, 2) would be empty


@pytest.mark.parametrize("shape", SHAPES)
def test_bucket_mix(shape):
    W, d = shape
    xs = _xs(shape, jnp.float32)
    m = jax.random.uniform(jax.random.PRNGKey(1), (max(1, W // 2), W))
    m = m / m.sum(1, keepdims=True)
    np.testing.assert_allclose(
        bucket_mix(m, xs), ref.bucket_mix(m, xs), rtol=1e-5, atol=1e-4
    )


@pytest.mark.parametrize("shape", SHAPES)
def test_residual_norms(shape):
    W, d = shape
    xs = _xs(shape, jnp.float32)
    c = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (W,)))
    np.testing.assert_allclose(
        residual_norms(xs, c), ref.residual_norms(xs, c), rtol=1e-4, atol=1e-3
    )


@pytest.mark.parametrize("shape", SHAPES)
def test_cclip_combine(shape):
    W, d = shape
    xs = _xs(shape, jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (d,))
    lam = jax.random.uniform(jax.random.PRNGKey(4), (W,))
    np.testing.assert_allclose(
        cclip_combine(xs, v, lam), ref.cclip_combine(xs, v, lam), rtol=1e-5, atol=1e-4
    )


@pytest.mark.parametrize("block_d", [128, 512, 4096])
def test_block_size_invariance(block_d):
    """Results must not depend on the BlockSpec tiling."""
    xs = _xs((16, 3000), jnp.float32)
    np.testing.assert_allclose(
        pairwise_gram(xs, block_d=block_d), ref.pairwise_gram(xs), rtol=1e-5, atol=1e-3
    )
    np.testing.assert_allclose(
        cwise_median(xs, block_d=block_d), ref.cwise_median(xs), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        cwise_trimmed_mean(xs, 3, block_d=block_d), ref.cwise_trimmed_mean(xs, 3),
        rtol=1e-6, atol=1e-6,
    )


# (W, R, C, seg): two steps over a partial last row block; leaf rows fewer
# than 8 with a step of zeros only; two worker groups, the second partial
PACK_CASES = [(1, 9, 128, 1280), (12, 6, 768, 6144), (25, 20, 200, 6144)]


@pytest.mark.parametrize("W,R,C,seg", PACK_CASES)
def test_pack_rows_writes_lane_aligned_rows(W, R, C, seg):
    """``pack_rows`` writes the plain layout: each worker's rows, zero-padded
    from C to whole lane tiles, one after another; zero rows up to the
    8-row multiple; zeros up to ``seg``. The rest of an aliased buffer is
    left as it was."""
    from repro.kernels.pack_rows import pack_rows

    x = _xs((W, R, C), jnp.float32, seed=R)
    Wp, Cp = max(8, -(-W // 8) * 8), -(-C // 128) * 128
    want = np.zeros((Wp, seg), np.float32)
    want[:W, :R * Cp] = np.pad(np.asarray(x), ((0, 0), (0, 0), (0, Cp - C))
                               ).reshape(W, -1)
    fresh = pack_rows(x, (Wp, 128 + seg), off=128, seg=seg)
    np.testing.assert_array_equal(np.asarray(fresh)[:, 128:], want)
    before = jnp.full((Wp, 128 + seg + 256), 7.0, jnp.float32)
    after = np.asarray(pack_rows(x, before, off=128, seg=seg))
    np.testing.assert_array_equal(after[:, 128:128 + seg], want)
    assert (after[:, :128] == 7.0).all() and (after[:, 128 + seg:] == 7.0).all()


# --------------------------------------------------- composed aggregator ops
def test_ops_rfa_aggregate_matches_ref():
    xs = _xs((21, 1500), jnp.float32)
    np.testing.assert_allclose(
        ops.rfa_aggregate(xs), ref.rfa_aggregate(xs), rtol=1e-4, atol=1e-4
    )


def test_ops_cclip_aggregate_matches_ref():
    xs = _xs((15, 900), jnp.float32)
    np.testing.assert_allclose(
        ops.cclip_aggregate(xs, 5.0), ref.cclip_aggregate(xs, 5.0), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize("shape", [(10, 1000), (53, 257)])
def test_residual_norms_explicit_center(shape):
    """center=v is the pseudo-row-free path: ||x_i - v||^2 without building
    a [W+1, d] stack."""
    W, d = shape
    xs = _xs(shape, jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(5), (d,), jnp.float32)
    expect = jnp.sum((xs - v[None, :]) ** 2, axis=1)
    np.testing.assert_allclose(
        residual_norms(xs, center=v), expect, rtol=1e-4, atol=1e-3
    )
    with pytest.raises(ValueError):
        residual_norms(xs)
    with pytest.raises(ValueError):
        c = jnp.full((W,), 1.0 / W, jnp.float32)
        residual_norms(xs, c, center=v)


@pytest.mark.parametrize("shape", [(10, 1000), (25, 4097)])
def test_cclip_fused_iter_matches_two_pass(shape):
    """Fused kernel == separate combine + residual-norm passes."""
    from repro.kernels import cclip_fused_iter

    W, d = shape
    xs = _xs(shape, jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(6), (d,), jnp.float32)
    lam = jax.random.uniform(jax.random.PRNGKey(7), (W,))
    v_new, r2 = cclip_fused_iter(xs, v, lam)
    expect_v = ref.cclip_combine(xs, v, lam)
    np.testing.assert_allclose(v_new, expect_v, rtol=1e-5, atol=1e-4)
    expect_r2 = jnp.sum((xs - expect_v[None, :]) ** 2, axis=1)
    np.testing.assert_allclose(r2, expect_r2, rtol=1e-4, atol=1e-3)


def test_gram_acc_chaining_bit_exact():
    """Chained per-segment Gram calls (acc + full_blocks) == one call on the
    concatenated block-aligned buffer, BIT for bit — the packed/per-leaf
    bridge."""
    bd = 256
    xs1 = _xs((12, bd * 2), jnp.float32, seed=11)
    xs2 = _xs((12, bd * 3), jnp.float32, seed=12)
    chained = pairwise_gram(xs1, block_d=bd, full_blocks=True)
    chained = pairwise_gram(xs2, chained, block_d=bd, full_blocks=True)
    packed = pairwise_gram(jnp.concatenate([xs1, xs2], axis=1), block_d=bd)
    np.testing.assert_array_equal(np.asarray(chained), np.asarray(packed))


def test_ops_match_core_aggregators(key):
    """Kernel path == the repro.core implementations used by the trainer."""
    from repro.core.aggregators import RFA, CenteredClip, CoordinateWiseMedian

    xs = jax.random.normal(key, (13, 700)) * 2
    np.testing.assert_allclose(
        ops.cm_aggregate(xs), CoordinateWiseMedian().aggregate(xs), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        ops.rfa_aggregate(xs, n_iters=8), RFA(n_iters=8).aggregate(xs),
        rtol=1e-4, atol=1e-4,
    )
    np.testing.assert_allclose(
        ops.cclip_aggregate(xs, 3.0, n_iters=3),
        CenteredClip(tau=3.0, n_iters=3).aggregate(xs),
        rtol=1e-4, atol=1e-4,
    )


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("Sq,Skv,H,KV,window", [
    (64, 64, 4, 4, 0),       # MHA causal
    (64, 64, 8, 2, 0),       # GQA
    (64, 64, 4, 2, 24),      # sliding window
    (32, 128, 4, 4, 0),      # chunked prefill (q suffix of kv)
])
def test_flash_attention_matches_ref(Sq, Skv, H, KV, window):
    from repro.kernels.flash_attention import flash_attention
    key = jax.random.PRNGKey(0)
    B, dh = 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, KV, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, KV, dh), jnp.float32)
    out = flash_attention(q, k, v, window=window, block_q=16, block_kv=32)
    expect = ref.attention(q, k, v, window=window)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


def test_flash_attention_matches_model_blockwise():
    """Kernel == the pure-JAX blockwise impl used by the models layer."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models.attention import _attn_blockwise
    key = jax.random.PRNGKey(1)
    B, S, H, KV, dh = 1, 64, 4, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, dh), jnp.float32)
    out_kernel = flash_attention(q, k, v, block_q=16, block_kv=16)
    out_blockwise = _attn_blockwise(q, k, v, dh ** -0.5, True, 0, 16, 16)
    np.testing.assert_allclose(out_kernel, out_blockwise, rtol=2e-4, atol=2e-4)
