"""Step factories: train_step (grad-accum microbatching, remat, clipping,
optimizer) and the serving engine's paged prefill, paged decode and slot
admission.  These are what the launcher and the engine jit with the ASA
plan's shardings and what the dry-run lowers."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.optim import optimizers as O


# --- buffer-donation conventions -------------------------------------------
# Single source of truth for which positional arguments of each step kind
# are donated when jitted.  Every call site (serving/engine.py,
# runtime/trainer.py, launch/dryrun.py) and the tracecheck donation
# analyzer (analysis/tracecheck.py) read THIS table — a jit that donates
# anything else is either leaking HBM (undonated cache doubles the pool)
# or donating a buffer some caller still holds.
#
#   train:         (params, opt_state) are consumed and returned updated
#   paged prefill/decode, slot admission: the cache is the mutable carry;
#                  params are read-only weights and must NOT be donated
STEP_DONATION: dict[str, tuple[int, ...]] = {
    "train": (0, 1),
    "paged_prefill": (1,),
    "paged_decode": (1,),
    "slot_admit": (1,),
}


def jit_step(kind: str, fn, **jit_kwargs):
    """``jax.jit`` a step function with the donation convention for its
    kind.  ``jit_kwargs`` pass through (out_shardings, static_argnums, ...);
    a caller-supplied ``donate_argnums`` is rejected — the table is the
    convention, not a default."""
    if "donate_argnums" in jit_kwargs:
        raise ValueError("jit_step owns donate_argnums; "
                         f"use STEP_DONATION[{kind!r}]")
    return jax.jit(fn, donate_argnums=STEP_DONATION[kind], **jit_kwargs)


def make_loss_fn(arch: ArchConfig, *, impl="xla", remat="none",
                 act_sharding=None, mtp_weight: float = 0.3):
    def loss_fn(params, tokens, labels, frontend=None):
        out = T.lm_apply(params, arch, tokens, frontend=frontend, impl=impl,
                         remat=remat, act_sharding=act_sharding,
                         return_hidden=arch.mtp)
        loss = T.lm_loss(out.logits, labels, arch.vocab)
        if arch.mtp:
            # depth-1 MTP: hidden_t + emb(token_{t+1}) predicts token_{t+2}
            # = labels shifted left by one (mask the wrapped tail position)
            mtp_lg = T.mtp_logits(params, arch, out.hidden, tokens)
            tgt = jnp.roll(labels, -1, axis=1)
            mask = jnp.ones_like(tgt, jnp.float32).at[:, -1].set(0.0)
            loss = loss + mtp_weight * T.lm_loss(mtp_lg, tgt, arch.vocab, mask)
        return loss + out.aux, loss
    return loss_fn


def make_train_step(arch: ArchConfig, optimizer, *, microbatches: int = 1,
                    impl: str = "xla", remat: str = "none",
                    act_sharding=None, grad_shardings=None,
                    clip_norm: float = 1.0, mtp_weight: float = 0.3):
    """-> train_step(params, opt_state, batch) -> (params, opt_state, metrics)

    batch = {"tokens": (B,S) i32, "labels": (B,S) i32[, "frontend": (B,T,D)]}.
    Gradients are accumulated over `microbatches` slices of the batch via
    lax.scan (only one microbatch's activations live at a time).
    grad_shardings (pytree of NamedSharding, like params) pins per-microbatch
    gradients and the accumulator to the parameter layout — without it GSPMD
    replicates the scan carry and all-reduces full fp32 gradients every
    microbatch (observed: +66 GB/device on qwen3-8b, EXPERIMENTS.md §Perf).
    """
    _, opt_update = optimizer
    loss_fn = make_loss_fn(arch, impl=impl, remat=remat,
                           act_sharding=act_sharding, mtp_weight=mtp_weight)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def _pin(grads):
        if grad_shardings is None:
            return grads
        return jax.tree.map(jax.lax.with_sharding_constraint, grads,
                            grad_shardings)

    def one_microbatch(params, mb):
        (total, ce), grads = grad_fn(params, mb["tokens"], mb["labels"],
                                     mb.get("frontend"))
        return _pin(grads), total, ce

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            grads, total, ce = one_microbatch(params, batch)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            mbs = {k: split(v) for k, v in batch.items()}

            def body(acc, mb):
                g_acc, t_acc, c_acc = acc
                g, t, c = one_microbatch(params, mb)
                g_acc = _pin(jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) / microbatches,
                    g_acc, g))
                return (g_acc, t_acc + t / microbatches,
                        c_acc + c / microbatches), 0.0

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            (grads, total, ce), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), mbs)

        grads, gnorm = O.clip_by_global_norm(grads, clip_norm)
        updates, opt_state = opt_update(grads, opt_state, params)
        params = O.apply_updates(params, updates)
        metrics = {"loss": total, "ce": ce, "grad_norm": gnorm,
                   "step": opt_state.step}
        return params, opt_state, metrics

    return train_step


# --- paged steps (continuous-batching engine, repro/serving/) --------------
# All take the shared serving cache (attn block pools + slot-state pools,
# see transformer.init_paged_cache) plus per-sequence position vectors (B,),
# block tables (B, max_blocks) and slot ids (B,); see layers.paged_attention
# and mamba2.mamba2_slot.
#
# With ``sampler`` (serving.sampling.make_sampler) the steps fuse sampling
# on device: they take per-row (temperature, top_k, top_p, seed) arrays and
# return (token (B,), logprob (B,), cache) instead of logits — only a (B,)
# token vector crosses back to the host, and the sampling key is derived
# inside the jit from the absolute position of the produced token.

def make_paged_prefill_step(arch: ArchConfig, *, impl: str = "xla",
                            act_sharding=None, sampler=None):
    """-> prefill(params, cache, tokens (B,C), positions, block_tables,
    new_lens, slot_ids) -> (last_valid_logits (B,V), cache).  Called once
    per prompt *chunk* — the engine interleaves these with decode steps
    instead of stalling a wave.  ``new_lens`` (B,) is the real token count
    per row; the chunk is padded to a fixed C so the step traces once, and
    the returned logits are taken at row new_lens-1 (the last real token).
    ``slot_ids`` (B,) maps rows to slot-state pool rows (SSM state carried
    as h0 across chunks; cross K/V read-only).

    With ``sampler`` the signature gains (temperature, top_k, top_p, seeds)
    row arrays and returns (token (B,), logprob (B,), cache): the token
    after the chunk is sampled on device at absolute position
    ``positions + new_lens`` (only meaningful — and only consumed — on the
    final chunk of a prompt)."""
    def _last_logits(params, cache, tokens, positions, block_tables,
                     new_lens, slot_ids):
        out = T.lm_apply(params, arch, tokens, cache=cache,
                         positions=positions, block_tables=block_tables,
                         new_lens=new_lens, slot_ids=slot_ids, impl=impl,
                         act_sharding=act_sharding)
        last = jnp.take_along_axis(
            out.logits, (new_lens - 1)[:, None, None], axis=1)
        return last[:, 0], out.cache

    if sampler is None:
        return _last_logits

    def paged_prefill_step(params, cache, tokens, positions, block_tables,
                           new_lens, slot_ids, temperature, top_k, top_p,
                           seeds):
        last, cache = _last_logits(params, cache, tokens, positions,
                                   block_tables, new_lens, slot_ids)
        tok, logp = sampler(last, temperature, top_k, top_p, seeds,
                            positions + new_lens)
        return tok, logp, cache
    return paged_prefill_step


def make_paged_decode_step(arch: ArchConfig, *, impl: str = "xla",
                           act_sharding=None, sampler=None):
    """-> decode(params, cache, tokens (B,1), positions, block_tables,
    slot_ids) -> (logits (B,V), cache).  Every batch row advances at its
    *own* position — slots holding idle/prefilling requests point their
    block tables at the null block, their slot_ids at the null slot row,
    and are masked by the caller.

    With ``sampler`` the signature gains (temperature, top_k, top_p, seeds)
    row arrays and returns (token (B,), logprob (B,), cache): the next
    token is sampled on device at absolute position ``positions + 1`` (the
    input token lives at ``positions``)."""
    def _logits(params, cache, tokens, positions, block_tables, slot_ids):
        out = T.lm_apply(params, arch, tokens, cache=cache,
                         positions=positions, block_tables=block_tables,
                         slot_ids=slot_ids, impl=impl,
                         act_sharding=act_sharding)
        return out.logits[:, -1], out.cache

    if sampler is None:
        return _logits

    def paged_decode_step(params, cache, tokens, positions, block_tables,
                          slot_ids, temperature, top_k, top_p, seeds):
        logits, cache = _logits(params, cache, tokens, positions,
                                block_tables, slot_ids)
        tok, logp = sampler(logits, temperature, top_k, top_p, seeds,
                            positions + 1)
        return tok, logp, cache
    return paged_decode_step


def make_slot_admit_step(arch: ArchConfig):
    """-> admit(params, cache, slot_id[, frontend]) -> cache.  Resets one
    engine slot's rows in every slot-state pool on admission: mamba2 state
    zeroed; cross-attn K/V zeroed or computed once from the request's
    ``frontend`` patch embeddings (1, T, d_model); wdec encoder K/V zeroed
    or computed by running the encoder ONCE over the request's frame
    embeddings (whisper admission — see transformer.admit_slot).  No-op for
    paged block pools."""
    def slot_admit_step(params, cache, slot_id, frontend=None):
        return T.admit_slot(params, arch, cache, slot_id, frontend=frontend)
    return slot_admit_step
