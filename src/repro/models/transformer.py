"""StackedLM: composes an ArchConfig's segment pattern into init/apply.

Homogeneous segments are `lax.scan`ned over their repeat count (params stacked
on a leading axis) to keep HLO size and dry-run compile time bounded for
54-100-layer architectures.  Heterogeneous patterns (hybrid/VLM) are segments
whose body applies several block kinds in order.

Entry points:
  init_lm(key, arch)                          -> params
  init_paged_cache(arch, num_blocks, block_size, dtype, slots=...) -> pools
  lm_apply(params, arch, tokens, ...)         -> LMOutput(logits, cache, aux, hidden)
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks as B
from repro.models import layers as L

Params = dict
Array = jax.Array


class LMOutput(NamedTuple):
    logits: Array
    cache: Optional[Any]
    aux: Array                       # scalar auxiliary loss (MoE balance, ...)
    hidden: Optional[Array] = None   # pre-head hidden states (for MTP)


def _compute_dtype(arch: ArchConfig):
    return jnp.bfloat16 if arch.dtype == "bfloat16" else jnp.float32


def _param_dtype(arch: ArchConfig):
    return jnp.float32 if arch.param_dtype == "float32" else jnp.bfloat16


def sinusoidal_at(positions: Array, d_model: int) -> Array:
    """Sinusoidal embeddings for arbitrary integer positions: (S,) -> (S, D)."""
    pos = positions.astype(jnp.float32)[:, None]
    dim = jnp.arange(0, d_model, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d_model)
    pe = jnp.zeros((positions.shape[0], d_model))
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    # odd d_model: only floor(d/2) cos columns exist, angle has ceil(d/2)
    pe = pe.at[:, 1::2].set(jnp.cos(angle[:, : d_model // 2]))
    return pe


def sinusoidal_positions(seq_len: int, d_model: int) -> Array:
    return sinusoidal_at(jnp.arange(seq_len), d_model)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_lm(key, arch: ArchConfig) -> Params:
    dt = _param_dtype(arch)
    n_seg = len(arch.pattern)
    ks = jax.random.split(key, n_seg + 5)
    params: Params = {
        "embed": L.init_embedding(ks[0], arch.padded_vocab, arch.d_model, dt),
        "final_norm": B.norm_init(arch, arch.d_model, dt),
    }
    if not arch.tie_embeddings:
        params["head"] = L.init_dense(ks[1], arch.d_model, arch.padded_vocab, dtype=dt)
    if any("shared_attn" in seg.blocks for seg in arch.pattern):
        params["shared"] = B.init_shared(ks[2], arch, dt)
    if arch.encoder is not None:
        enc_keys = jax.random.split(ks[3], 1)[0]
        params["encoder"] = {
            "segments": [_init_segment(enc_keys, ("enc_attn",),
                                       arch.encoder.n_layers, arch, dt)],
            "final_norm": B.norm_init(arch, arch.d_model, dt),
        }
    if arch.mtp:
        params["mtp"] = {
            "proj": L.init_dense(ks[4], 2 * arch.d_model, arch.d_model, dtype=dt),
            "block": B.init_block(jax.random.fold_in(ks[4], 1), "attn", arch, dt),
            "norm": B.norm_init(arch, arch.d_model, dt),
        }
    params["segments"] = [
        _init_segment(ks[5 + i], seg.blocks, seg.repeat, arch, dt)
        for i, seg in enumerate(arch.pattern)
    ]
    return params


def _init_segment(key, blocks: tuple, repeat: int, arch: ArchConfig, dt) -> Params:
    """Params stacked along a leading `repeat` axis (scan xs)."""
    def one(k):
        kk = jax.random.split(k, len(blocks))
        return {f"b{i}": B.init_block(kk[i], kind, arch, dt)
                for i, kind in enumerate(blocks)}
    return jax.vmap(one)(jax.random.split(key, repeat))


def init_paged_cache(arch: ArchConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, *, slots: int = 0) -> list:
    """Per-segment stacked serving cache pools (leading repeat axis).

    Each segment's stack is the carry of its layer scan (_apply_segment),
    updated in place at each layer's index: a step writes its new rows and
    nothing else of the pools.

    Two state classes, side by side (serving/cache_manager.py is the host
    side of both):
      * attn-family blocks — including zamba2's shared block (per-
        application pools via this function's repeat stacking) and MLA's
        latent cache — get *paged block pools*: no batch axis; the pool is
        shared by every in-flight request and indexed through per-request
        block tables (layers.paged_attention, mla.mla_paged_attention);
      * mamba2 / cross_attn blocks get *slot-indexed state pools* — leading
        axis ``slots + 1`` (O(1)-per-request state: one row per engine slot
        plus a reserved null row for inactive batch rows).  ``slots`` must
        be > 0 when the pattern contains such blocks.  wdec blocks carry
        both: a paged self-attn pool and a slot-state encoder-K/V pool."""
    caches = []
    for seg in arch.pattern:
        def one(_):
            return {f"b{i}": B.init_paged_block_cache(kind, arch, num_blocks,
                                                      block_size, dtype,
                                                      slots=slots)
                    for i, kind in enumerate(seg.blocks)}
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[one(r) for r in range(seg.repeat)]) \
            if seg.repeat > 1 else jax.tree.map(lambda x: x[None], one(0))
        caches.append(stacked)
    return caches


def encode_frontend(params: Params, arch: ArchConfig, frontend: Array, *,
                    impl: str = "xla", remat: str = "none",
                    act_sharding=None) -> Array:
    """Run the fixed-length encoder stack over precomputed frame embeddings
    (B, enc_len, d_model) -> encoder output (B, enc_len, d_model).  Shared
    by the training forward (lm_apply's audio branch) and by serving
    admission (admit_slot runs it ONCE per request, never per step)."""
    cdt = _compute_dtype(arch)
    enc = frontend.astype(cdt)
    enc = enc + sinusoidal_positions(enc.shape[1], arch.d_model).astype(cdt)
    enc_p = params["encoder"]
    for segp in enc_p["segments"]:
        enc, _, _ = _apply_segment(segp, ("enc_attn",), arch, enc,
                                   impl=impl, remat=remat,
                                   act_sharding=act_sharding)
    return B.norm_apply(arch, enc_p["final_norm"], enc)


def _scatter_cross_kv(pool: Params, slot_id, attn_stack: Params,
                      cfg, src: Array) -> Params:
    """Project ``src`` (T, d_model) through each application's wk/wv (params
    stacked over the segment repeat axis) and write the result into this
    slot's rows of a (repeat, slots+1, T, Hkv, D) cross-K/V pool.  Shared by
    the cross_attn (vision frontend) and wdec (encoder output) admission
    branches so the projection convention cannot drift between them."""
    def kv_of(pl, cfg=cfg, f=src):
        k = L.dense(pl["wk"], f).reshape(-1, cfg.n_kv_heads, cfg.head_dim)
        v = L.dense(pl["wv"], f).reshape(-1, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            k = L.rmsnorm(pl["k_norm"], k)
        return k, v

    k, v = jax.vmap(kv_of)(attn_stack)                       # (repeat, T, ..)
    return {"k": pool["k"].at[:, slot_id].set(k.astype(pool["k"].dtype)),
            "v": pool["v"].at[:, slot_id].set(v.astype(pool["v"].dtype))}


def admit_slot(params: Params, arch: ArchConfig, pools: list, slot_id,
               frontend: Optional[Array] = None) -> list:
    """Reset one engine slot's rows across every slot-state pool (paged KV
    block pools pass through untouched — block reuse is handled by the
    allocator instead).

    mamba2 rows are zeroed (fresh recurrent state for the admitted request;
    recompute-style preemption re-admits through here, so the re-prefill
    starts from a clean h0).  cross_attn rows are zeroed, or — when the
    admitted request carries ``frontend`` patch embeddings (1, T, d_model) —
    filled with the cross K/V projections computed *once* here, never again
    per step.  wdec rows get the encoder cross K/V: ``frontend`` frame
    embeddings (1, enc_len, d_model) run through the encoder stack once,
    then every decoder layer's cross projections are written into this
    slot's rows.  Without a frontend the rows are zeroed: the request is
    served with zero cross K/V, as the greedy goldens were recorded."""
    cdt = _compute_dtype(arch)
    enc_out = None
    if frontend is not None and \
            any("wdec" in seg.blocks for seg in arch.pattern):
        enc_out = encode_frontend(params, arch, frontend)[0]     # (T, D)
    out = []
    for si, seg in enumerate(arch.pattern):
        segp = params["segments"][si]
        d = {}
        for bi, kind in enumerate(seg.blocks):
            key = f"b{bi}"
            pool = pools[si][key]
            if kind == "wdec":
                cross = pool["cross"]
                if enc_out is None:
                    newc = jax.tree.map(lambda t: t.at[:, slot_id].set(0.0),
                                        cross)
                else:
                    cfg = B.attn_cfg_for(arch, causal=False, use_rope=False)
                    newc = _scatter_cross_kv(cross, slot_id,
                                             segp[key]["xattn"], cfg,
                                             enc_out.astype(cdt))
                d[key] = {"self": pool["self"], "cross": newc}
            elif kind == "mamba2":
                d[key] = jax.tree.map(lambda t: t.at[:, slot_id].set(0.0),
                                      pool)
            elif kind == "cross_attn":
                if frontend is None:
                    d[key] = jax.tree.map(lambda t: t.at[:, slot_id].set(0.0),
                                          pool)
                else:
                    cfg = B.attn_cfg_for(arch, causal=False, gated=True,
                                         use_rope=False)
                    d[key] = _scatter_cross_kv(pool, slot_id,
                                               segp[key]["attn"], cfg,
                                               frontend[0].astype(cdt))
            else:
                d[key] = pool
        out.append(d)
    return out


# ---------------------------------------------------------------------------
# apply
# ---------------------------------------------------------------------------

REMAT_POLICIES = {
    "none": None,
    "full": "everything",
    "selective": "dots",        # save matmul outputs w/o batch dims (MaxText-style)
}


def _constrain(x, act_sharding):
    """Pin the layer-boundary activation sharding (GSPMD loses the batch
    sharding inside checkpointed scan bodies otherwise — production
    frameworks always constrain layer inputs)."""
    if act_sharding is None or x is None:
        return x
    return jax.lax.with_sharding_constraint(x, act_sharding)


def _apply_segment(seg_params, blocks, arch, x, *, seg_cache=None, x0=None,
                   cross_input=None, shared=None, positions=None,
                   block_tables=None, new_lens=None, slot_ids=None,
                   impl="xla",
                   unroll: int = 1, remat: str = "none", act_sharding=None):
    """Scan the segment body over its repeat axis.  ``remat`` applies
    per-layer activation checkpointing inside the scan (the standard
    scan-over-layers + remat pattern — O(1) activation memory in depth).

    With ``seg_cache`` the segment's stacked cache is the scan *carry*, not
    a scanned input: each layer gets its index as the scan input and its
    blocks update the carry in place at that index (B.apply_block's
    ``layer``), so a step writes only its new rows and never slices a
    layer's pool out, writes it back whole into a stacked result, or copies
    that result after the loop.  The carry is the step's donated cache
    argument (runtime/steps.py STEP_DONATION)."""
    has_cache = seg_cache is not None

    def body(carry, xs):
        if has_cache:
            (x, aux, cache), (p_stack, layer) = carry, xs
        else:
            (x, aux), p_stack = carry, xs
            cache = layer = None
        x = _constrain(x, act_sharding)
        for i, kind in enumerate(blocks):
            bi = f"b{i}"
            x, nc, a = B.apply_block(
                p_stack[bi], kind, arch, x, x0=x0, cross_input=cross_input,
                shared=shared, cache=cache[bi] if has_cache else None,
                positions=positions, block_tables=block_tables,
                new_lens=new_lens, slot_ids=slot_ids, layer=layer, impl=impl)
            if has_cache:
                cache = {**cache, bi: nc}
            aux = aux + a
        x = _constrain(x, act_sharding)
        if has_cache:
            return (x, aux, cache), None
        return (x, aux), B.ZERO

    if not has_cache:
        if remat != "none":
            policy = (None if remat == "full" else
                      jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
            body = jax.checkpoint(body, policy=policy)
        (x, aux), _ = jax.lax.scan(body, (x, B.ZERO), seg_params,
                                   unroll=unroll)
        return x, aux, None
    depth = jax.tree.leaves(seg_params)[0].shape[0]
    (x, aux, seg_cache), _ = jax.lax.scan(
        body, (x, B.ZERO, seg_cache),
        (seg_params, jnp.arange(depth, dtype=jnp.int32)), unroll=unroll)
    return x, aux, seg_cache


def lm_apply(params: Params, arch: ArchConfig, tokens: Optional[Array] = None, *,
             cache: Optional[list] = None,
             frontend: Optional[Array] = None,
             positions: Optional[Array] = None,
             block_tables: Optional[Array] = None,
             new_lens: Optional[Array] = None,
             slot_ids: Optional[Array] = None,
             impl: str = "xla",
             remat: str = "none",
             act_sharding=None,
             return_hidden: bool = False) -> LMOutput:
    """Forward pass.

    tokens: (B, S) int32 — LM/decoder tokens (None for pure-frontend encoders).
    cache:  per-segment stacked serving pools (init_paged_cache); None =>
       training forward.
    frontend: precomputed modality embeddings —
       vlm:   (B, n_img_tokens, d_model) patch embeddings -> cross-attn input
       audio: (B, enc_len, d_model) frame embeddings -> encoder input
    block_tables: (B, max_blocks) int32 — the rows' pages in the block
       pools; required with ``cache``, with per-sequence ``positions`` (B,).
       ``new_lens`` (B,) marks token rows past it as padding (fixed-shape
       prompt chunks; see layers.paged_attention).
    slot_ids: (B,) int32 — pool rows for the slot-indexed state pools
       (mamba2 state, cross-attn K/V); inactive batch rows point at the
       reserved null row (= slots).  Required alongside block_tables when
       the pattern contains slot-state blocks.
    """
    cdt = _compute_dtype(arch)
    aux_total = B.ZERO

    cross_input = None
    if arch.frontend == "vision" and frontend is not None:
        cross_input = frontend.astype(cdt)
    if arch.frontend == "audio" and frontend is not None:
        cross_input = encode_frontend(params, arch, frontend, impl=impl,
                                      remat=remat, act_sharding=act_sharding)

    x = L.embed(params["embed"], tokens, arch.d_model).astype(cdt)
    if arch.encoder is not None:   # whisper decoder: absolute sinusoidal positions
        if cache is None:
            pe = sinusoidal_positions(x.shape[1], arch.d_model)
        else:
            # serving: each batch row decodes at its own absolute
            # position, so the PE is per-row (B, S, D)
            pe = jax.vmap(lambda p0: sinusoidal_at(
                p0 + jnp.arange(x.shape[1]), arch.d_model))(positions)
        x = x + pe.astype(cdt)

    if positions is None and cache is None:
        positions = jnp.arange(x.shape[1])

    x = _constrain(x, act_sharding)
    x0 = x  # original embeddings (zamba2 shared-block input)
    new_caches = []
    for si, seg in enumerate(arch.pattern):
        seg_cache = cache[si] if cache is not None else None
        x, aux, nc = _apply_segment(
            params["segments"][si], seg.blocks, arch, x,
            seg_cache=seg_cache, x0=x0, cross_input=cross_input,
            shared=params.get("shared"), positions=positions,
            block_tables=block_tables, new_lens=new_lens, slot_ids=slot_ids,
            impl=impl, remat=remat, act_sharding=act_sharding)
        aux_total = aux_total + aux
        new_caches.append(nc)

    hidden = B.norm_apply(arch, params["final_norm"], x)
    if arch.tie_embeddings:
        logits = L.unembed(params["embed"], hidden)
    else:
        logits = L.dense(params["head"], hidden).astype(jnp.float32)

    return LMOutput(logits, new_caches if cache is not None else None,
                    aux_total, hidden if return_hidden else None)


def mtp_logits(params: Params, arch: ArchConfig, hidden: Array,
               tokens: Array) -> Array:
    """DeepSeek-V3-style multi-token prediction head (depth 1): combine the
    final hidden state at position t with the embedding of token t+1 to
    predict token t+2.  Returns logits (B, S, V) aligned so that
    logits[:, t] predicts tokens[:, t+2]."""
    mtp = params["mtp"]
    cdt = hidden.dtype
    emb_next = L.embed(params["embed"], jnp.roll(tokens, -1, axis=1),
                       arch.d_model).astype(cdt)
    h = L.dense(mtp["proj"], jnp.concatenate(
        [B.norm_apply(arch, mtp["norm"], hidden), emb_next], axis=-1))
    h, _, _ = B.apply_block(mtp["block"], "attn", arch, h,
                            positions=jnp.arange(h.shape[1]))
    return L.unembed(params["embed"], h) if arch.tie_embeddings \
        else L.dense(params["head"], h).astype(jnp.float32)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def lm_loss(logits: Array, labels: Array, vocab: int,
            mask: Optional[Array] = None) -> Array:
    """Cross-entropy with padded-vocab masking (labels < vocab always).

    Vocab-parallel formulation: only reductions touch the (possibly
    `model`-sharded) vocab axis — no gather, so GSPMD lowers to partial
    reductions + tiny (B,S) all-reduces instead of all-gathering the fp32
    logits (Megatron's vocab-parallel cross-entropy)."""
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    vid = jax.lax.broadcasted_iota(jnp.int32, (1, 1, V), 2)
    if V > vocab:   # mask padding logits out of the softmax
        logits = jnp.where(vid < vocab, logits, -1e30)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
    tgt_mask = vid == labels[..., None]
    tgt = jnp.sum(jnp.where(tgt_mask, logits, 0.0), axis=-1)
    nll = lse - tgt
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
