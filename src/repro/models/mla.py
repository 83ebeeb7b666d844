"""Multi-head Latent Attention (DeepSeek-V3, arXiv:2412.19437).

Queries go through a low-rank down/up projection; keys/values are generated
from a compressed latent ``c_kv`` (kv_lora_rank) plus a shared rotary key
``k_rope``.  Decode caches only ``(c_kv, k_rope)`` — ~(512+64) floats/token
instead of 2*128*128 for vanilla MHA — which is the whole point of MLA.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L

Params = dict
Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def init_mla(key, cfg: MLAConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 8)
    H = cfg.n_heads
    return {
        "wq_a": L.init_dense(ks[0], cfg.d_model, cfg.q_lora_rank, dtype=dtype),
        "q_norm": L.init_rmsnorm(cfg.q_lora_rank, dtype),
        "wq_b": L.init_dense(ks[1], cfg.q_lora_rank, H * cfg.qk_head_dim, dtype=dtype),
        "wkv_a": L.init_dense(ks[2], cfg.d_model,
                              cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype=dtype),
        "kv_norm": L.init_rmsnorm(cfg.kv_lora_rank, dtype),
        "wk_b": L.init_dense(ks[3], cfg.kv_lora_rank, H * cfg.qk_nope_head_dim, dtype=dtype),
        "wv_b": L.init_dense(ks[4], cfg.kv_lora_rank, H * cfg.v_head_dim, dtype=dtype),
        "wo": L.init_dense(ks[5], H * cfg.v_head_dim, cfg.d_model, dtype=dtype,
                           scale=1.0 / math.sqrt(H * cfg.v_head_dim)),
    }


def init_paged_mla_cache(cfg: MLAConfig, num_blocks: int, block_size: int,
                         dtype=jnp.bfloat16) -> Params:
    """Physical latent block pools shared by all requests (no batch axis;
    block 0 is the reserved null block).  MLA's whole point — caching only
    (c_kv, k_rope) per token — carries over to paging: a block holds
    block_size latent rows instead of block_size KV head vectors."""
    return {"c_kv": jnp.zeros((num_blocks, block_size, cfg.kv_lora_rank),
                              dtype),
            "k_rope": jnp.zeros((num_blocks, block_size,
                                 cfg.qk_rope_head_dim), dtype)}


def _project_q(p, cfg: MLAConfig, x, positions):
    B, S, _ = x.shape
    q = L.dense(p["wq_b"], L.rmsnorm(p["q_norm"], L.dense(p["wq_a"], x)))
    q = q.reshape(B, S, cfg.n_heads, cfg.qk_head_dim)
    q_nope = q[..., : cfg.qk_nope_head_dim]
    q_rope = L.apply_rope(q[..., cfg.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


MLA_CHUNK = 512


def _attend(cfg: MLAConfig, q_nope, q_rope, c_kv, k_rope, p, *,
            q_positions, kv_len=None):
    """Latent-space attention: score via up-projected keys, value from c_kv.

    q_nope: (B,S,H,dn)  q_rope: (B,S,H,dr)  c_kv: (B,T,r)  k_rope: (B,T,dr)
    q_positions: (S,) shared across the batch (no cache) or (B,S) per-row
    (paged serving); kv_len: None or (B,) per-row.
    Absorbed form: score_nope = (q_nope @ wk_b^T) @ c_kv^T — contracts in the
    rank-r latent space, so no per-token key materialization (decode-fast).
    Long sequences scan over q blocks (logits memory B*H*C*T, not B*H*S*T).
    """
    B, S, H, dn = q_nope.shape
    T = c_kv.shape[1]
    wk = p["wk_b"]["w"].reshape(cfg.kv_lora_rank, H, dn)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk.astype(q_nope.dtype))
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    kp = jnp.arange(T)
    ckv = c_kv.astype(q_nope.dtype)
    krope = k_rope.astype(q_rope.dtype)
    qpb = jnp.broadcast_to(q_positions, (B, S)) \
        if q_positions.ndim == 1 else q_positions             # (B, S)

    def block(q_lat_b, q_rope_b, pos_b):
        s_nope = jnp.einsum("bshr,btr->bhst", q_lat_b, ckv)
        s_rope = jnp.einsum("bshd,btd->bhst", q_rope_b, krope)
        lg = (s_nope + s_rope).astype(jnp.float32) * scale
        mask = pos_b[:, :, None] >= kp[None, None, :]         # (B, C, T)
        if kv_len is not None:
            mask = mask & (kp[None, None, :] < kv_len[:, None, None])
        lg = jnp.where(mask[:, None], lg, -1e30)
        pr = jax.nn.softmax(lg, axis=-1).astype(ckv.dtype)
        return jnp.einsum("bhst,btr->bshr", pr, ckv)      # latent context

    if S * T > 1024 * 1024 and S > MLA_CHUNK:
        C = MLA_CHUNK
        pad = (-S) % C
        qlp = jnp.pad(q_lat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qrp = jnp.pad(q_rope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pp = jnp.pad(qpb, ((0, 0), (0, pad)), constant_values=-1)
        nq = qlp.shape[1] // C
        xs = (jnp.moveaxis(qlp.reshape(B, nq, C, H, -1), 1, 0),
              jnp.moveaxis(qrp.reshape(B, nq, C, H, -1), 1, 0),
              jnp.moveaxis(pp.reshape(B, nq, C), 1, 0))
        _, ys = jax.lax.scan(lambda _, x: (0.0, block(*x)), 0.0, xs)
        ctx_lat = jnp.moveaxis(ys, 0, 1).reshape(B, nq * C, H, -1)[:, :S]
    else:
        ctx_lat = block(q_lat, q_rope, qpb)

    wv = p["wv_b"]["w"].reshape(cfg.kv_lora_rank, H, cfg.v_head_dim)
    ctx = jnp.einsum("bshr,rhv->bshv", ctx_lat.astype(q_nope.dtype),
                     wv.astype(q_nope.dtype))
    return ctx.reshape(B, S, H * cfg.v_head_dim)


def mla_paged_attention(p: Params, cfg: MLAConfig, x: Array, *,
                        cache: Params, positions: Array,
                        block_tables: Array,
                        new_lens: Optional[Array] = None,
                        layer: Optional[Array] = None
                        ) -> tuple[Array, Params]:
    """Latent attention over block-paged (c_kv, k_rope) pools — the MLA
    analogue of layers.paged_attention, same flat-index scheme: new latents
    scatter at block_tables[b, pos // BS] * BS + pos % BS, out-of-table and
    padded-row writes divert to the null block, and attention runs over the
    gathered logical view with per-sequence causal/length masks.  With
    ``layer`` the pools are the stacked scan carry, written and gathered in
    place at that layer (layers.paged_write_gather).  Masked entries
    contribute exactly-zero probability, so each row attends over its own
    prefix only."""
    B, S, _ = x.shape
    BS = cache["c_kv"].shape[-2]
    kv = L.dense(p["wkv_a"], x)
    c_kv = L.rmsnorm(p["kv_norm"], kv[..., : cfg.kv_lora_rank])
    k_rope_new = kv[..., cfg.kv_lora_rank:]
    qp, flat = L.paged_flat_indices(positions, S, block_tables, BS,
                                    new_lens=new_lens)
    k_rope_new = L.apply_rope(k_rope_new[:, :, None, :], qp,
                              cfg.rope_theta)[:, :, 0, :]
    flat = flat.reshape(-1)
    cc, g_ckv = L.paged_write_gather(cache["c_kv"], c_kv.reshape(B * S, -1),
                                     flat, block_tables, layer)
    cr, g_rope = L.paged_write_gather(cache["k_rope"],
                                      k_rope_new.reshape(B * S, -1), flat,
                                      block_tables, layer)
    q_nope, q_rope = _project_q(p, cfg, x, qp)
    kv_len = positions + (new_lens if new_lens is not None else S)
    ctx = _attend(cfg, q_nope, q_rope, g_ckv, g_rope, p,
                  q_positions=qp, kv_len=kv_len)
    return L.dense(p["wo"], ctx), {"c_kv": cc, "k_rope": cr}


def mla_attention(p: Params, cfg: MLAConfig, x: Array, *,
                  positions: Optional[Array] = None) -> Array:
    """Latent attention over the call's own tokens (training, and the
    forward without a cache); serving goes through
    :func:`mla_paged_attention`."""
    S = x.shape[1]
    kv = L.dense(p["wkv_a"], x)
    c_kv = L.rmsnorm(p["kv_norm"], kv[..., : cfg.kv_lora_rank])
    if positions is None:
        positions = jnp.arange(S)
    k_rope = L.apply_rope(kv[..., cfg.kv_lora_rank:][:, :, None, :],
                          positions, cfg.rope_theta)[:, :, 0, :]
    q_nope, q_rope = _project_q(p, cfg, x, positions)
    ctx = _attend(cfg, q_nope, q_rope, c_kv, k_rope, p,
                  q_positions=positions)
    return L.dense(p["wo"], ctx)
