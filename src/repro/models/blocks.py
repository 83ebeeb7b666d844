"""Block registry: init / apply / cache-init per block kind.

Blocks are the ASA's *logical components* (DESIGN.md §1): the scheduler
assigns a parallelism strategy per block kind per segment.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import mla as MLA
from repro.models import moe as MOE

Params = dict
Array = jax.Array

# a numpy scalar: a jnp one would open the device when this module loads
ZERO = np.zeros((), np.float32)


def norm_init(arch: ArchConfig, d: int, dtype) -> Params:
    return L.init_layernorm(d, dtype) if arch.norm == "layernorm" else L.init_rmsnorm(d, dtype)


def norm_apply(arch: ArchConfig, p: Params, x: Array) -> Array:
    return L.layernorm(p, x) if arch.norm == "layernorm" else L.rmsnorm(p, x)


def attn_cfg_for(arch: ArchConfig, *, causal=True, gated=False, d_model=None,
                 n_heads=None, use_rope=True) -> L.AttnConfig:
    nh = n_heads or arch.n_heads
    dm = d_model or arch.d_model
    hd = arch.resolved_head_dim if d_model is None else dm // nh
    n_kv = min(arch.n_kv_heads, nh) if d_model is None else nh
    return L.AttnConfig(
        d_model=dm, n_heads=nh, n_kv_heads=n_kv, head_dim=hd,
        rope_theta=arch.rope_theta, use_rope=use_rope and arch.rope_theta > 0,
        qk_norm=arch.qk_norm, causal=causal, bias=arch.attn_bias, gated=gated)


def moe_cfg_for(arch: ArchConfig) -> MOE.MoEConfig:
    m = arch.moe
    return MOE.MoEConfig(
        d_model=arch.d_model, d_ff=m.d_ff, n_experts=m.n_experts, top_k=m.top_k,
        router=m.router, capacity_factor=m.capacity_factor,
        n_shared_experts=m.n_shared_experts, shared_d_ff=m.shared_d_ff,
        dense_d_ff=m.dense_d_ff, act=arch.act)


def ssm_cfg_for(arch: ArchConfig) -> M2.Mamba2Config:
    s = arch.ssm
    return M2.Mamba2Config(d_model=arch.d_model, d_state=s.d_state,
                           head_dim=s.head_dim, expand=s.expand,
                           n_groups=s.n_groups, d_conv=s.d_conv, chunk=s.chunk)


def mla_cfg_for(arch: ArchConfig) -> MLA.MLAConfig:
    m = arch.mla
    return MLA.MLAConfig(d_model=arch.d_model, n_heads=arch.n_heads,
                         q_lora_rank=m.q_lora_rank, kv_lora_rank=m.kv_lora_rank,
                         qk_nope_head_dim=m.qk_nope_head_dim,
                         qk_rope_head_dim=m.qk_rope_head_dim,
                         v_head_dim=m.v_head_dim, rope_theta=arch.rope_theta)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(key, kind: str, arch: ArchConfig, dtype) -> Params:
    d = arch.d_model
    ks = jax.random.split(key, 6)
    if kind == "attn":
        return {"norm1": norm_init(arch, d, dtype),
                "attn": L.init_attention(ks[0], attn_cfg_for(arch), dtype),
                "norm2": norm_init(arch, d, dtype),
                "mlp": L.init_mlp(ks[1], d, arch.d_ff, act=arch.act, dtype=dtype)}
    if kind == "enc_attn":
        cfg = attn_cfg_for(arch, causal=False, use_rope=False)
        dff = arch.encoder.d_ff if arch.encoder else arch.d_ff
        return {"norm1": norm_init(arch, d, dtype),
                "attn": L.init_attention(ks[0], cfg, dtype),
                "norm2": norm_init(arch, d, dtype),
                "mlp": L.init_mlp(ks[1], d, dff, act=arch.act, dtype=dtype)}
    if kind == "moe_attn":
        return {"norm1": norm_init(arch, d, dtype),
                "attn": L.init_attention(ks[0], attn_cfg_for(arch), dtype),
                "norm2": norm_init(arch, d, dtype),
                "moe": MOE.init_moe(ks[1], moe_cfg_for(arch), dtype)}
    if kind == "mla":
        return {"norm1": norm_init(arch, d, dtype),
                "attn": MLA.init_mla(ks[0], mla_cfg_for(arch), dtype),
                "norm2": norm_init(arch, d, dtype),
                "moe": MOE.init_moe(ks[1], moe_cfg_for(arch), dtype)}
    if kind == "mla_dense":
        return {"norm1": norm_init(arch, d, dtype),
                "attn": MLA.init_mla(ks[0], mla_cfg_for(arch), dtype),
                "norm2": norm_init(arch, d, dtype),
                "mlp": L.init_mlp(ks[1], d, arch.d_ff, act=arch.act, dtype=dtype)}
    if kind == "mamba2":
        return {"norm": norm_init(arch, d, dtype),
                "mixer": M2.init_mamba2(ks[0], ssm_cfg_for(arch), dtype)}
    if kind == "cross_attn":
        cfg = attn_cfg_for(arch, causal=False, gated=True, use_rope=False)
        return {"norm1": norm_init(arch, d, dtype),
                "attn": L.init_attention(ks[0], cfg, dtype),
                "norm2": norm_init(arch, d, dtype),
                "mlp": L.init_mlp(ks[1], d, arch.d_ff, act=arch.act, dtype=dtype),
                "mlp_gate": jnp.zeros((), dtype)}
    if kind == "wdec":
        self_cfg = attn_cfg_for(arch, causal=True, use_rope=False)
        cross_cfg = attn_cfg_for(arch, causal=False, use_rope=False)
        return {"norm1": norm_init(arch, d, dtype),
                "attn": L.init_attention(ks[0], self_cfg, dtype),
                "norm2": norm_init(arch, d, dtype),
                "xattn": L.init_attention(ks[1], cross_cfg, dtype),
                "norm3": norm_init(arch, d, dtype),
                "mlp": L.init_mlp(ks[2], d, arch.d_ff, act=arch.act, dtype=dtype)}
    if kind == "shared_attn":
        # zamba2: per-application params only (projection of the shared block's
        # 2d-wide output back to d); the shared weights live in init_shared().
        return {"app_proj": L.init_dense(ks[0], 2 * d, d, dtype=dtype)}
    raise ValueError(f"unknown block kind {kind!r}")


def init_shared(key, arch: ArchConfig, dtype) -> Params:
    """Zamba2 shared transformer block over concat(x, x0) — width 2*d."""
    d2 = 2 * arch.d_model
    cfg = attn_cfg_for(arch, d_model=d2, n_heads=arch.n_heads)
    ks = jax.random.split(key, 2)
    return {"norm1": norm_init(arch, d2, dtype),
            "attn": L.init_attention(ks[0], cfg, dtype),
            "norm2": norm_init(arch, d2, dtype),
            "mlp": L.init_mlp(ks[1], d2, arch.d_ff, act=arch.act, dtype=dtype)}


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_paged_block_cache(kind: str, arch: ArchConfig, num_blocks: int,
                           block_size: int, dtype=jnp.bfloat16, *,
                           slots: int = 0) -> Params:
    """Serving cache pool for one block (continuous-batching engine).

    attn-family kinds — including zamba2's weight-shared block (its pool is
    stacked per *application* by init_paged_cache's repeat axis, so each of
    the shared block's applications pages its own KV) and MLA's latent
    (c_kv, k_rope) cache — get a physical *block pool* (length-indexed,
    paged through block tables).  mamba2 / cross_attn state is O(1) per
    request — not length-indexed, so paging does not apply; they get a
    *slot-indexed state pool* instead: ``slots`` rows plus a trailing
    reserved null row (see models/mamba2.mamba2_slot).  whisper's wdec
    carries both classes: paged self-attn KV plus a slot-state pool holding
    the per-request encoder cross K/V (written once at admission)."""
    if kind in ("attn", "moe_attn"):
        return L.init_paged_attention_cache(attn_cfg_for(arch), num_blocks,
                                            block_size, dtype)
    if kind == "shared_attn":
        cfg = attn_cfg_for(arch, d_model=2 * arch.d_model,
                           n_heads=arch.n_heads)
        return L.init_paged_attention_cache(cfg, num_blocks, block_size,
                                            dtype)
    if kind in ("mla", "mla_dense"):
        return MLA.init_paged_mla_cache(mla_cfg_for(arch), num_blocks,
                                        block_size, dtype)
    if kind in ("mamba2", "cross_attn", "wdec"):
        if slots <= 0:
            raise ValueError(
                f"slot-state pool for {kind!r} needs slots > 0 (one state "
                f"row per engine slot + the null row)")
        if kind == "mamba2":
            return M2.init_mamba2_cache(ssm_cfg_for(arch), slots + 1)
        if kind == "wdec":
            if arch.encoder is None:
                raise ValueError(
                    f"{arch.name}: wdec blocks need arch.encoder (its "
                    f"seq_len sizes the per-slot cross-K/V pool)")
            self_cfg = attn_cfg_for(arch, use_rope=False)
            cross_cfg = attn_cfg_for(arch, causal=False, use_rope=False)
            enc_len = arch.encoder.seq_len
            shp = (slots + 1, enc_len, cross_cfg.n_kv_heads,
                   cross_cfg.head_dim)
            return {"self": L.init_paged_attention_cache(
                        self_cfg, num_blocks, block_size, dtype),
                    "cross": {"k": jnp.zeros(shp, dtype),
                              "v": jnp.zeros(shp, dtype)}}
        cfg = attn_cfg_for(arch, causal=False, use_rope=False)
        shp = (slots + 1, arch.n_img_tokens, cfg.n_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}
    raise ValueError(f"no paged/slot-state serving cache for block kind "
                     f"{kind!r}")


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

def apply_block(p: Params, kind: str, arch: ArchConfig, x: Array, *,
                x0: Optional[Array] = None,
                cross_input: Optional[Array] = None,
                shared: Optional[Params] = None,
                cache: Optional[Params] = None,
                positions: Optional[Array] = None,
                block_tables: Optional[Array] = None,
                new_lens: Optional[Array] = None,
                slot_ids: Optional[Array] = None,
                layer: Optional[Array] = None,
                impl: str = "xla"):
    """-> (x, new_cache, aux_loss).  ``cache`` is the block's serving pool
    (transformer.init_paged_cache), or None for training and the encoder.
    ``block_tables`` selects the paged-KV path for attn-family kinds;
    ``slot_ids`` selects the slot-state pool path for mamba2 / cross_attn
    (see serving/cache_manager.py).

    With ``layer`` (an int32 scalar) ``cache`` is the block's pool stacked
    over the segment's layers — the scan carry of
    transformer._apply_segment — read and written in place at (layer, row),
    and the stacked pool is returned."""
    aux = ZERO
    if (block_tables is not None or slot_ids is not None) and \
            kind not in ("attn", "moe_attn", "mamba2", "cross_attn",
                         "mla", "mla_dense", "shared_attn", "wdec"):
        raise ValueError(f"continuous-batching serving unsupported for block "
                         f"kind {kind!r}")
    if kind in ("attn", "enc_attn", "moe_attn"):
        causal = kind != "enc_attn"
        cfg = attn_cfg_for(arch, causal=causal, use_rope=(kind != "enc_attn"))
        h, new_cache = L.attention(p["attn"], cfg, norm_apply(arch, p["norm1"], x),
                                   cache=cache, positions=positions,
                                   block_tables=block_tables,
                                   new_lens=new_lens, layer=layer, impl=impl)
        x = x + h
        if kind == "moe_attn":
            h, aux = MOE.moe(p["moe"], moe_cfg_for(arch),
                             norm_apply(arch, p["norm2"], x))
        else:
            h = L.mlp(p["mlp"], norm_apply(arch, p["norm2"], x), arch.act)
        return x + h, new_cache, aux

    if kind in ("mla", "mla_dense"):
        if block_tables is not None:
            h, new_cache = MLA.mla_paged_attention(
                p["attn"], mla_cfg_for(arch),
                norm_apply(arch, p["norm1"], x), cache=cache,
                positions=positions, block_tables=block_tables,
                new_lens=new_lens, layer=layer)
        else:
            h = MLA.mla_attention(p["attn"], mla_cfg_for(arch),
                                  norm_apply(arch, p["norm1"], x),
                                  positions=positions)
            new_cache = None
        x = x + h
        if kind == "mla":
            h, aux = MOE.moe(p["moe"], moe_cfg_for(arch),
                             norm_apply(arch, p["norm2"], x))
        else:
            h = L.mlp(p["mlp"], norm_apply(arch, p["norm2"], x), arch.act)
        return x + h, new_cache, aux

    if kind == "mamba2":
        normed = norm_apply(arch, p["norm"], x)
        if slot_ids is not None:
            h, new_cache = M2.mamba2_slot(p["mixer"], ssm_cfg_for(arch),
                                          normed, pool=cache,
                                          slot_ids=slot_ids,
                                          new_lens=new_lens, layer=layer,
                                          impl=impl)
        else:
            h, new_cache = M2.mamba2(p["mixer"], ssm_cfg_for(arch), normed,
                                     impl=impl)
        return x + h, new_cache, aux

    if kind == "cross_attn":
        cfg = attn_cfg_for(arch, causal=False, gated=True, use_rope=False)
        if slot_ids is not None:
            # slot-state pool: per-request cross K/V rows are read-only here
            # (written once at admission — transformer.admit_slot)
            at = L.layer_index(layer, slot_ids)
            rows = {"k": cache["k"][at], "v": cache["v"][at]}
            h, _ = L.attention(p["attn"], cfg,
                               norm_apply(arch, p["norm1"], x),
                               cache=rows, impl=impl)
            new_cache = cache
        else:
            h, new_cache = L.attention(p["attn"], cfg,
                                       norm_apply(arch, p["norm1"], x),
                                       kv_input=cross_input, impl=impl)
        x = x + h
        h = L.mlp(p["mlp"], norm_apply(arch, p["norm2"], x), arch.act)
        x = x + jnp.tanh(p["mlp_gate"].astype(h.dtype)) * h
        return x, new_cache, aux

    if kind == "wdec":
        self_cfg = attn_cfg_for(arch, causal=True, use_rope=False)
        cross_cfg = attn_cfg_for(arch, causal=False, use_rope=False)
        h, nc_self = L.attention(p["attn"], self_cfg,
                                 norm_apply(arch, p["norm1"], x),
                                 cache=None if cache is None else cache["self"],
                                 positions=positions,
                                 block_tables=block_tables,
                                 new_lens=new_lens, layer=layer, impl=impl)
        x = x + h
        if slot_ids is not None:
            # slot-state pool: per-request encoder cross K/V rows are
            # read-only here (written once at admission —
            # transformer.admit_slot runs the encoder)
            at = L.layer_index(layer, slot_ids)
            rows = {"k": cache["cross"]["k"][at], "v": cache["cross"]["v"][at]}
            h, _ = L.attention(p["xattn"], cross_cfg,
                               norm_apply(arch, p["norm2"], x),
                               cache=rows, impl=impl)
        else:
            h, _ = L.attention(p["xattn"], cross_cfg,
                               norm_apply(arch, p["norm2"], x),
                               kv_input=cross_input, impl=impl)
        x = x + h
        h = L.mlp(p["mlp"], norm_apply(arch, p["norm3"], x), arch.act)
        new_cache = (None if cache is None
                     else {"self": nc_self, "cross": cache["cross"]})
        return x + h, new_cache, aux

    if kind == "shared_attn":
        assert shared is not None and x0 is not None
        d2 = 2 * arch.d_model
        cfg = attn_cfg_for(arch, d_model=d2, n_heads=arch.n_heads)
        z = jnp.concatenate([x, x0], axis=-1)
        # block_tables route to the per-application paged pool (``layer``
        # picks this application's pool out of the repeat-stacked ones, so
        # weight sharing never mixes two applications' KV)
        h, new_cache = L.attention(shared["attn"], cfg,
                                   norm_apply(arch, shared["norm1"], z),
                                   cache=cache, positions=positions,
                                   block_tables=block_tables,
                                   new_lens=new_lens, layer=layer, impl=impl)
        z = z + h
        z = z + L.mlp(shared["mlp"], norm_apply(arch, shared["norm2"], z), arch.act)
        return x + L.dense(p["app_proj"], z), new_cache, aux

    raise ValueError(kind)
