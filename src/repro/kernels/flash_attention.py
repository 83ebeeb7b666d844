"""Flash attention — Pallas TPU kernel.

Online-softmax tiling: grid (B, H, num_q_blocks, num_kv_blocks) with the kv
axis innermost (sequential).  Per-invocation VMEM working set:

    q     (block_q, d)     — revisited across the kv axis (index_map pins j)
    k, v  (block_k, d)     — streamed HBM->VMEM per kv block
    acc   (block_q, d) f32 + m,l (block_q,) f32 scratch — persist across kv

Causal blocks above the diagonal are skipped with pl.when (the MXU never
sees them — this is the 2x-flops win over the XLA fallback path).
block_q = block_k = 128 keeps every matmul dim MXU-aligned (128x128).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  seq_q: int, seq_k: int):
    i = pl.program_id(2)            # q block
    j = pl.program_id(3)            # kv block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * block_q
    k_start = j * block_k
    # causal: skip blocks entirely above the diagonal
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_k
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(j == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, scale: float | None = None,
                    causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool | None = None):
    """q: (B,H,S,D); k,v: (B,H,T,D) — kv pre-expanded to q heads.
    Returns (B,H,S,D) in q.dtype."""
    B, H, S, D = q.shape
    T = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = default_interpret()

    bq, bk = min(block_q, S), min(block_k, T)
    pad_q, pad_k = (-S) % bq, (-T) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq, nk = q.shape[2] // bq, k.shape[2] // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_q=S, seq_k=T)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),        # running max m
            pltpu.VMEM((bq,), jnp.float32),        # running sum l
            pltpu.VMEM((bq, D), jnp.float32),      # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S] if pad_q else out
