"""Grouped-query contraction in attention.

``layers._paged_sdpa`` and ``layers._sdpa`` contract each KV head with its
own group of query heads instead of broadcasting K/V to every query head.
These tests hold ``paged_attention`` and ``_sdpa`` to the broadcast form
they replaced (kept here as the reference), and check that a lowered GQA
paged decode step no longer carries a K/V broadcast to n_heads.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models import transformer as T
from repro.runtime import steps as ST
from serving_fixtures import TINY

N_KV, HEAD_DIM, BLOCK, N_BLOCKS = 2, 16, 4, 16


def _broadcast_heads(t, n_heads):
    """(B,T,Hkv,D) -> (B,T,H,D): each kv head copied to its query group."""
    return jnp.repeat(t, n_heads // t.shape[2], axis=2)


def _broadcast_sdpa(q, k, v, *, scale, q_pos, kv_len):
    """The paged SDPA before grouping: K/V broadcast to all H query heads,
    then one head-wise contraction."""
    B, S, H, D = q.shape
    k, v = _broadcast_heads(k, H), _broadcast_heads(v, H)
    T_ = k.shape[1]
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    kp = jnp.arange(T_)
    mask = q_pos[:, :, None] >= kp[None, None, :]
    mask = mask & (kp[None, None, :] < kv_len[:, None, None])
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


# decode: one new token per row; prefill: an 8-token chunk, rows padded
# past new_lens (the engine's fixed chunk shape)
MODES = {
    "decode": dict(seq=1, positions=[0, 7, 6], new_lens=None),
    "prefill": dict(seq=8, positions=[0, 4, 0], new_lens=[8, 3, 5]),
}
TABLES = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 0, 0]]


def _paged_call(cfg, mode, dtype):
    """-> jitted () -> (y, cache) for one paged_attention call on a pool
    whose every row (the null block's too) holds random K/V."""
    m = MODES[mode]
    kp, kx, kk, kv = jax.random.split(jax.random.PRNGKey(7), 4)
    params = L.init_attention(kp, cfg, dtype)
    pool_shape = (N_BLOCKS, BLOCK, cfg.n_kv_heads, cfg.head_dim)
    cache = {"k": jax.random.normal(kk, pool_shape).astype(dtype),
             "v": jax.random.normal(kv, pool_shape).astype(dtype)}
    x = jax.random.normal(kx, (len(TABLES), m["seq"], cfg.d_model)).astype(dtype)
    positions = jnp.array(m["positions"], jnp.int32)
    tables = jnp.array(TABLES, jnp.int32)
    new_lens = (None if m["new_lens"] is None
                else jnp.array(m["new_lens"], jnp.int32))
    return jax.jit(lambda: L.paged_attention(
        params, cfg, x, cache=cache, positions=positions,
        block_tables=tables, new_lens=new_lens))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("group", [1, 2, 4])
def test_grouped_paged_attention_matches_broadcast_form(group, mode, dtype,
                                                        monkeypatch):
    cfg = L.AttnConfig(d_model=64, n_heads=N_KV * group, n_kv_heads=N_KV,
                       head_dim=HEAD_DIM, qk_norm=True)
    y, cache = _paged_call(cfg, mode, jnp.dtype(dtype))()
    monkeypatch.setattr(L, "_paged_sdpa", _broadcast_sdpa)
    y_ref, cache_ref = _paged_call(cfg, mode, jnp.dtype(dtype))()

    assert y.shape == y_ref.shape and y.dtype == y_ref.dtype
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache[name]),
                                      np.asarray(cache_ref[name]))
    got, want = np.asarray(y, np.float32), np.asarray(y_ref, np.float32)
    assert np.isfinite(got).all()
    if dtype == "bfloat16":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _broadcast_dense_sdpa(q, k, v, *, causal, scale):
    """``_sdpa`` before grouping: K/V broadcast to all H query heads, one
    head-wise contraction over the whole (S, T) logits."""
    H = q.shape[2]
    k, v = _broadcast_heads(k, H), _broadcast_heads(v, H)
    S, T_ = q.shape[1], k.shape[1]
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    if causal:
        logits = jnp.where(jnp.arange(S)[:, None] >= jnp.arange(T_)[None, :],
                           logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


# dense: a short sequence; chunked: one past SDPA_CHUNK_THRESHOLD**2 logits,
# not a multiple of SDPA_CHUNK, so the last query block is padded
SDPA_SEQ = {"dense": 24, "chunked": L.SDPA_CHUNK_THRESHOLD + 16}


@pytest.mark.parametrize("form", sorted(SDPA_SEQ))
@pytest.mark.parametrize("group", [1, 2, 4])
def test_grouped_sdpa_matches_broadcast_form(group, form):
    S = SDPA_SEQ[form]
    assert (S * S > L.SDPA_CHUNK_THRESHOLD ** 2 and S > L.SDPA_CHUNK) \
        == (form == "chunked")
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (2, S, N_KV * group, HEAD_DIM))
    k = jax.random.normal(kk, (2, S, N_KV, HEAD_DIM))
    v = jax.random.normal(kv, (2, S, N_KV, HEAD_DIM))
    for causal in (True, False):
        got = jax.jit(lambda q, k, v: L._sdpa(
            q, k, v, causal=causal, scale=0.25))(q, k, v)
        want = _broadcast_dense_sdpa(q, k, v, causal=causal, scale=0.25)
        assert got.shape == want.shape == q.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its params
    (scan and while bodies, pjit, custom calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _kv_broadcasts(arch, slots, table_len):
    """Shapes of the broadcast_in_dim outputs in the lowered paged decode
    step that put n_heads on the gathered K/V's head axis: (B,T,H,D), or
    (B,T,Hkv,G,D) before the reshape."""
    B, T_ = slots, table_len * BLOCK
    H, Hkv, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
    bad = {(B, T_, H, D), (B, T_, Hkv, H // Hkv, D)}
    params = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), arch))
    cache = jax.eval_shape(lambda: T.init_paged_cache(
        arch, N_BLOCKS, BLOCK, jnp.float32))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    jaxpr = jax.make_jaxpr(ST.make_paged_decode_step(arch))(
        params, cache, i32(B, 1), i32(B), i32(B, table_len), i32(B))
    return [tuple(e.outvars[0].aval.shape) for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name == "broadcast_in_dim"
            and tuple(e.outvars[0].aval.shape) in bad]


@pytest.mark.parametrize("group", [2, 4])
def test_paged_decode_step_has_no_kv_broadcast_to_query_heads(group,
                                                              monkeypatch):
    arch = dataclasses.replace(TINY, n_heads=N_KV * group, n_kv_heads=N_KV,
                               head_dim=HEAD_DIM)
    assert _kv_broadcasts(arch, slots=3, table_len=5) == []
    # the detector sees the broadcast form it replaced: K and V, once in the
    # scanned layer body
    monkeypatch.setattr(L, "_paged_sdpa", _broadcast_sdpa)
    assert len(_kv_broadcasts(arch, slots=3, table_len=5)) == 2
