"""SSD (Mamba-2) scan: chunked dual form == naive recurrence oracle."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import ssm


def naive_ssd(x, dt, A, B_, C_):
    """Direct O(S) recurrence: h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;
    y_t = h_t C_t. Shapes as ssd_scan."""
    Bsz, S, H, P = x.shape
    N = B_.shape[-1]
    Bh = B_[:, :, 0]  # [B,S,N] (G=1)
    Ch = C_[:, :, 0]
    h = jnp.zeros((Bsz, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        dA = jnp.exp(dt[:, t] * A[None, :])  # [B,H]
        upd = jnp.einsum("bh,bhp,bn->bhpn", dt[:, t], x[:, t].astype(jnp.float32),
                         Bh[:, t].astype(jnp.float32))
        h = h * dA[..., None, None] + upd
        ys.append(jnp.einsum("bhpn,bn->bhp", h, Ch[:, t].astype(jnp.float32)))
    return jnp.stack(ys, axis=1), h  # [B,S,H,P], [B,H,P,N]


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_ssd_scan_matches_naive_recurrence(key, chunk):
    Bsz, S, H, P, N = 2, 16, 3, 4, 8
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (Bsz, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    B_ = jax.random.normal(ks[3], (Bsz, S, 1, N))
    C_ = jax.random.normal(jax.random.fold_in(key, 9), (Bsz, S, 1, N))

    y_chunk, h_chunk = ssm.ssd_scan(x, dt, A, B_, C_, chunk)
    y_naive, h_naive = naive_ssd(x, dt, A, B_, C_)
    np.testing.assert_allclose(y_chunk, y_naive, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(h_chunk, h_naive, rtol=1e-4, atol=1e-4)


def test_ssd_scan_chunk_invariance(key):
    """Different chunk sizes give identical results."""
    Bsz, S, H, P, N = 1, 32, 2, 4, 4
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (Bsz, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B_ = jax.random.normal(ks[3], (Bsz, S, 1, N))
    C_ = jax.random.normal(ks[4], (Bsz, S, 1, N))
    y4, _ = ssm.ssd_scan(x, dt, A, B_, C_, 4)
    y32, _ = ssm.ssd_scan(x, dt, A, B_, C_, 32)
    np.testing.assert_allclose(y4, y32, rtol=1e-4, atol=1e-4)


def test_segsum_exp_structure():
    da = jnp.asarray([[0.1, -0.2, 0.3]])
    L = ssm._segsum_exp(da)[0]
    assert L.shape == (3, 3)
    # strictly upper triangle is zero; diagonal is exp(0)=1
    np.testing.assert_allclose(jnp.diagonal(L), 1.0, rtol=1e-6)
    assert float(L[0, 1]) == 0.0
    # L[2,0] = exp(da_1 + da_2)  (decay from step 0 to 2 excludes da_0)
    np.testing.assert_allclose(L[2, 0], jnp.exp(-0.2 + 0.3), rtol=1e-6)


def test_ssd_scan_grad_finite_for_strong_decay(key):
    """A chunk whose total decay passes exp's fp32 range (dt*|A|*Q > 88)
    must still give finite gradients: the masked upper triangle of the
    decay matrix may not leak 0 * inf into dt and A."""
    Bsz, S, H, P, N = 1, 64, 2, 4, 8
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (Bsz, S, H, P))
    B_ = jax.random.normal(ks[1], (Bsz, S, 1, N))
    C_ = jax.random.normal(ks[2], (Bsz, S, 1, N))
    dt = jnp.full((Bsz, S, H), 2.0)

    def loss(x, dt, A):
        return jnp.sum(ssm.ssd_scan(x, dt, A, B_, C_, S)[0])

    grads = jax.grad(loss, argnums=(0, 1, 2))(x, dt, -jnp.ones((H,)))
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))


def test_causal_conv_is_causal(key):
    B, S, C, K = 1, 10, 6, 4
    x = jax.random.normal(key, (B, S, C))
    w = jax.random.normal(jax.random.fold_in(key, 1), (C, K))
    b = jnp.zeros((C,))
    y1 = ssm._causal_conv(x, w, b)
    x2 = x.at[:, -1].set(0.0)
    y2 = ssm._causal_conv(x2, w, b)
    np.testing.assert_allclose(y1[:, :-1], y2[:, :-1], rtol=1e-5, atol=1e-6)


def test_ssm_layer_decode_matches_train(key):
    """Layer-level: step-by-step decode equals the chunked train path."""
    cfg = smoke_config("mamba2-130m")
    cfg = dataclasses.replace(cfg, dtype="float32")
    p = ssm.init_ssm(key, cfg)
    B, S = 1, 12
    h = jax.random.normal(jax.random.fold_in(key, 2), (B, S, cfg.d_model)) * 0.3
    full = ssm.ssm_layer(p, h, cfg)

    cache = ssm.init_ssm_cache(B, cfg, jnp.float32)
    for t in range(S):
        out, cache = ssm.decode_ssm(p, h[:, t:t + 1], cache, cfg)
        np.testing.assert_allclose(out[:, 0], full[:, t], rtol=3e-3, atol=3e-3)


# --------------------------------------------------- hypothesis properties
pytest.importorskip("hypothesis")  # absent in some environments
from hypothesis import given, settings, strategies as st


@given(S=st.sampled_from([8, 16, 24]), H=st.integers(1, 4),
       P=st.sampled_from([2, 4]), N=st.sampled_from([2, 8]),
       seed=st.integers(0, 1000))
@settings(max_examples=15, deadline=None)
def test_ssd_scan_property_matches_naive(S, H, P, N, seed):
    """Chunked SSD == naive recurrence for arbitrary shapes (property)."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    Bsz = 1
    x = jax.random.normal(ks[0], (Bsz, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bsz, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B_ = jax.random.normal(ks[3], (Bsz, S, 1, N))
    C_ = jax.random.normal(ks[4], (Bsz, S, 1, N))
    chunk = 8 if S % 8 == 0 else S
    y_c, h_c = ssm.ssd_scan(x, dt, A, B_, C_, chunk)
    y_n, h_n = naive_ssd(x, dt, A, B_, C_)
    np.testing.assert_allclose(y_c, y_n, rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(h_c, h_n, rtol=5e-4, atol=5e-4)
