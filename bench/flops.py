"""Operations and bytes that the algorithm needs, from shapes alone.

Each function takes a configuration file's published keys.  Model FLOPs
count multiply-adds as two operations and leave recomputation out.
"""
from __future__ import annotations

import numpy as np

BF16 = 2


def _layers(cfg: dict) -> int:
    return cfg.get("num_hidden_layers", cfg.get("n_layer"))


# ---------------------------------------------------------------------------
# Qwen3 (dense attention decoder)
# ---------------------------------------------------------------------------

def qwen3_layer_params(cfg: dict) -> int:
    d, H, Hkv, D = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return 2 * d * H * D + 2 * d * Hkv * D + 3 * d * cfg["intermediate_size"]


def qwen3_token_flops(cfg: dict, ctx, head: bool = True):
    """FLOPs of one token that attends over ``ctx`` positions (itself
    included); ``ctx`` may be an array.  ``head``: the token's logits are
    needed (the last prompt token and every decoded one)."""
    L, H, D = _layers(cfg), cfg["num_attention_heads"], cfg["head_dim"]
    f = 2 * L * qwen3_layer_params(cfg) + 4 * L * H * D * np.asarray(ctx)
    return f + (2 * cfg["hidden_size"] * cfg["vocab_size"] if head else 0)


def qwen3_kv_bytes_per_token(cfg: dict) -> int:
    return (_layers(cfg) * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * BF16)


def qwen3_weight_bytes(cfg: dict) -> int:
    """Weights one decode step reads: every layer and the head (the
    embedding is gathered a row per token)."""
    return BF16 * (_layers(cfg) * qwen3_layer_params(cfg)
                   + cfg["hidden_size"] * cfg["vocab_size"])


def qwen3_decode_cost(cfg: dict, ctx) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step whose decoding rows attend over
    ``ctx`` positions each (their new token included).  Bytes: the weights
    once, each row's live K/V, and the K/V the step writes."""
    ctx = np.asarray(ctx, np.float64)
    kv = qwen3_kv_bytes_per_token(cfg)
    flops = float(np.sum(qwen3_token_flops(cfg, ctx)))
    nbytes = qwen3_weight_bytes(cfg) + kv * float(np.sum(ctx)) + kv * ctx.size
    return flops, nbytes


def qwen3_prefill_flops(cfg: dict, start: int, n: int, last: bool) -> float:
    """FLOPs of prefilling prompt tokens start .. start+n-1; ``last``: the
    chunk ends the prompt, so its last token needs the head."""
    ctx = np.arange(start + 1, start + n + 1, dtype=np.float64)
    f = float(np.sum(qwen3_token_flops(cfg, ctx, head=False)))
    return f + (2 * cfg["hidden_size"] * cfg["vocab_size"] if last else 0)


# ---------------------------------------------------------------------------
# Mamba-2 (state-space duality), training
# ---------------------------------------------------------------------------

def mamba2_train_flops_per_token(cfg: dict) -> float:
    """Forward and backward (three times the forward) of one token.  Per
    layer: the z, x, B, C, dt and output projections, the three causal
    convolutions, and the chunked scan in its causal form (C.B and the
    intra-chunk product over half a chunk on average, the state read and
    the state update); the tied head once."""
    d, L = cfg["d_model"], _layers(cfg)
    E, N, G = cfg["expand"] * d, cfg["d_state"], cfg["ngroups"]
    P, K, Q = cfg["headdim"], cfg["d_conv"], cfg["chunk_size"]
    H = E // P
    proj = 2 * (d * (2 * E + 2 * G * N + H) + E * d)
    conv = 2 * K * (E + 2 * G * N)
    scan = Q * N * G + Q * H * P + 4 * H * P * N
    fwd = L * (proj + conv + scan) + 2 * d * cfg["vocab_size"]
    return 3.0 * fwd
