"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.mamba2 import Mamba2Config, _ssd_chunked

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (2, 256, 4, 2, 64),
    (1, 300, 2, 2, 128),     # non-multiple-of-block seq
    (2, 128, 8, 1, 32),      # MQA
    (1, 512, 4, 4, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, S, H, Hkv, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v)
    kx = jnp.repeat(k, H // Hkv, axis=2)
    vx = jnp.repeat(v, H // Hkv, axis=2)
    expected = ref.flash_attention_ref(q, kx, vx, scale=1.0 / np.sqrt(D))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("B,S,H,P,N,G,chunk", [
    (2, 64, 4, 16, 8, 1, 32),
    (1, 100, 2, 8, 16, 2, 32),    # ragged seq, multi-group
    (2, 33, 4, 32, 64, 1, 16),
])
def test_ssd_scan_matches_sequential_ref(B, S, H, P, N, G, chunk):
    cfg = Mamba2Config(d_model=H * P // 2, d_state=N, head_dim=P,
                       n_groups=G, chunk=chunk)
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, G, N))
    Cm = jax.random.normal(ks[2], (B, S, G, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[4], (B, S, H))) * dt
    y, hf = ops.ssd_scan(cfg, x, Bm, Cm, dt, a)
    hg = jnp.arange(H) // (H // G)
    yr, hr = ref.ssd_scan_ref(x, Bm[:, :, hg], Cm[:, :, hg], dt, a)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr),
                               atol=2e-3, rtol=2e-3)


def test_ssd_scan_carries_state():
    """Chunked scan over [0:S] == scan [0:k] then [k:S] with carried state."""
    cfg = Mamba2Config(d_model=32, d_state=8, head_dim=16, chunk=16)
    B, S, H, P, N = 1, 64, 4, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, 1, N))
    Cm = jax.random.normal(ks[2], (B, S, 1, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[4], (B, S, H))) * dt
    y_full, h_full = ops.ssd_scan(cfg, x, Bm, Cm, dt, a)
    k = 32
    y1, h1 = ops.ssd_scan(cfg, x[:, :k], Bm[:, :k], Cm[:, :k],
                          dt[:, :k], a[:, :k])
    y2, h2 = ops.ssd_scan(cfg, x[:, k:], Bm[:, k:], Cm[:, k:],
                          dt[:, k:], a[:, k:], h0=h1)
    np.testing.assert_allclose(np.asarray(y_full[:, k:]), np.asarray(y2),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h2),
                               atol=2e-3, rtol=2e-3)


def test_ssd_chunked_gradients_finite_at_fast_decay():
    """The XLA scan that training differentiates: with a decay fast enough
    that exp overflows above the chunk's diagonal (mamba2-780m's A reaches
    -48), its gradients stay finite and match the sequential oracle's."""
    cfg = Mamba2Config(d_model=32, d_state=8, head_dim=16, chunk=32)
    B, S, H, P, N = 1, 64, 4, 16, 8
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, 1, N))
    Cm = jax.random.normal(ks[2], (B, S, 1, N))
    dt = jnp.full((B, S, H), 0.1)
    a = -48.0 * jax.random.uniform(ks[3], (B, S, H), minval=0.5) * dt

    def loss(fn, x, a):
        y, h = fn(x, a)
        return jnp.sum(y ** 2) + jnp.sum(h ** 2)

    def chunked(x, a):
        return _ssd_chunked(cfg, x, Bm, Cm, (dt, a))

    def oracle(x, a):
        return ref.ssd_scan_ref(x, Bm, Cm, dt, a)

    got = jax.grad(functools.partial(loss, chunked), argnums=(0, 1))(x, a)
    want = jax.grad(functools.partial(loss, oracle), argnums=(0, 1))(x, a)
    for g, w in zip(got, want):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("shape", [(4, 37, 128), (2, 256), (1, 7, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    s = jax.random.normal(jax.random.fold_in(KEY, 1), (shape[-1],)) + 1.0
    out = ops.rmsnorm(x, s)
    expected = ref.rmsnorm_ref(x, s)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=tol, rtol=tol)
