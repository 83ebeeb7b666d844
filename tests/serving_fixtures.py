"""Tiny serving architectures + golden-parity scenarios, shared between
tests/test_serving.py and tests/gen_serving_goldens.py.

One tiny config per serving cache class the continuous engine supports:

  TINY         attention-only (paged KV block pools)
  TINY_SSM     pure mamba2 (slot-state pools only)
  TINY_HYBRID  attn + mamba2 (both state classes)
  TINY_CROSS   attn + gated cross-attn (llama-vision shape)
  TINY_SHARED  zamba2 shape: weight-shared 2*d attention block + mamba2
               (per-application paged KV pools for the shared block)
  TINY_ENCDEC  whisper shape: enc-dec wdec blocks (paged self-attn KV +
               slot-state cross K/V, encoder run once at admission)
  TINY_MLA     deepseek shape: latent-attention blocks with MoE FFNs
               (paged c_kv/k_rope latent pools)

All configs are float32 so greedy argmax parity is exact on CPU.
TINY_MLA's capacity_factor is set high enough that MoE token dropping can
never trigger: capacity is computed per (row, chunk) so a binding capacity
would make outputs depend on how a prompt is chunked — real deployments
accept that; the parity suite must not.

Each SCENARIOS entry pins the request set (prompts, per-request max_new,
slots, max_len) whose greedy outputs are frozen in goldens_serving.json —
captured from the retired wave Server (see gen_serving_goldens.py).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ArchConfig, EncoderSpec, MLASpec, MoESpec,
                                Segment, SSMSpec)
from repro.models import transformer as T
from repro.runtime import steps as ST
from repro.serving.sampling import make_sampler

GOLDENS_PATH = pathlib.Path(__file__).resolve().parent / "goldens_serving.json"

TINY = ArchConfig(name="tiny-serve", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                  pattern=(Segment(("attn",), 2),), dtype="float32",
                  param_dtype="float32")

TINY_SSM = ArchConfig(name="tiny-ssm", family="ssm", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
                      ssm=SSMSpec(d_state=16, head_dim=16, chunk=16),
                      pattern=(Segment(("mamba2",), 2),), dtype="float32",
                      param_dtype="float32")

TINY_HYBRID = ArchConfig(name="tiny-hybrid", family="hybrid", n_layers=4,
                         d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                         vocab=256,
                         ssm=SSMSpec(d_state=16, head_dim=16, d_conv=4,
                                     chunk=4),
                         pattern=(Segment(("attn", "mamba2"), 2),),
                         dtype="float32", param_dtype="float32")

TINY_CROSS = ArchConfig(name="tiny-cross", family="vlm", n_layers=4,
                        d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                        vocab=256, frontend="vision", n_img_tokens=8,
                        pattern=(Segment(("attn", "cross_attn"), 2),),
                        dtype="float32", param_dtype="float32")

TINY_SHARED = ArchConfig(name="tiny-shared", family="hybrid", n_layers=4,
                         d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                         vocab=256, act="geglu", tie_embeddings=True,
                         ssm=SSMSpec(d_state=16, head_dim=16, d_conv=4,
                                     chunk=4),
                         pattern=(Segment(("shared_attn", "mamba2"), 2),),
                         dtype="float32", param_dtype="float32")

TINY_ENCDEC = ArchConfig(name="tiny-encdec", family="audio", n_layers=2,
                         d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                         vocab=256, act="gelu", norm="layernorm",
                         attn_bias=True, tie_embeddings=True,
                         pattern=(Segment(("wdec",), 2),),
                         encoder=EncoderSpec(n_layers=2, seq_len=8, d_ff=128),
                         frontend="audio", dtype="float32",
                         param_dtype="float32")

TINY_MLA = ArchConfig(name="tiny-mla", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab=256,
                      mla=MLASpec(q_lora_rank=32, kv_lora_rank=16,
                                  qk_nope_head_dim=16, qk_rope_head_dim=8,
                                  v_head_dim=16),
                      moe=MoESpec(n_experts=2, top_k=1, d_ff=32,
                                  capacity_factor=16.0),
                      pattern=(Segment(("mla_dense",), 1),
                               Segment(("mla",), 1)),
                      dtype="float32", param_dtype="float32")

ARCH_BY_KEY = {"tiny": TINY, "ssm": TINY_SSM, "hybrid": TINY_HYBRID,
               "cross": TINY_CROSS, "shared": TINY_SHARED,
               "encdec": TINY_ENCDEC, "mla": TINY_MLA}


def scenario_prompts(plen: int, n: int) -> list[np.ndarray]:
    return [np.arange(1, plen + 1, dtype=np.int32) + i for i in range(n)]


# name -> request set + serving geometry.  max_new is a scalar (all requests)
# or a per-request list.  The wave Server that froze the goldens batched
# `slots` equal-length prompts per wave with decode bound `max_len`.
SCENARIOS: dict[str, dict] = {
    "tiny/base":      dict(arch="tiny", plen=8, n=5, max_new=6,
                           slots=2, max_len=64),
    "tiny/preempt":   dict(arch="tiny", plen=8, n=4, max_new=8,
                           slots=2, max_len=64),
    "tiny/victims":   dict(arch="tiny", plen=16, n=6, max_new=8,
                           slots=4, max_len=64),
    "tiny/mixed":     dict(arch="tiny", plen=8, n=2, max_new=[2, 20],
                           slots=2, max_len=12),
    "ssm/base":       dict(arch="ssm", plen=8, n=3, max_new=6,
                           slots=2, max_len=64),
    "hybrid/base":    dict(arch="hybrid", plen=8, n=4, max_new=6,
                           slots=2, max_len=64),
    "hybrid/preempt": dict(arch="hybrid", plen=8, n=4, max_new=8,
                           slots=2, max_len=64),
    "cross/base":     dict(arch="cross", plen=8, n=4, max_new=6,
                           slots=2, max_len=64),
    "shared/base":    dict(arch="shared", plen=8, n=4, max_new=6,
                           slots=2, max_len=64),
    "shared/preempt": dict(arch="shared", plen=8, n=4, max_new=8,
                           slots=2, max_len=64),
    "encdec/base":    dict(arch="encdec", plen=8, n=4, max_new=6,
                           slots=2, max_len=64),
    "encdec/preempt": dict(arch="encdec", plen=8, n=4, max_new=8,
                           slots=2, max_len=64),
    "mla/base":       dict(arch="mla", plen=8, n=4, max_new=6,
                           slots=2, max_len=64),
    "mla/preempt":    dict(arch="mla", plen=8, n=4, max_new=8,
                           slots=2, max_len=64),
}


def scenario_requests(name: str):
    """-> (arch, [(rid, prompt, max_new)], slots, max_len)."""
    sc = SCENARIOS[name]
    arch = ARCH_BY_KEY[sc["arch"]]
    prompts = scenario_prompts(sc["plen"], sc["n"])
    mn = sc["max_new"]
    max_news = mn if isinstance(mn, list) else [mn] * sc["n"]
    reqs = [(i, p, m) for i, (p, m) in enumerate(zip(prompts, max_news))]
    return arch, reqs, sc["slots"], sc["max_len"]


def load_goldens(name: str) -> dict[int, list[int]]:
    """Pinned greedy outputs for one scenario: {request id -> tokens}."""
    with open(GOLDENS_PATH) as f:
        data = json.load(f)
    return {int(k): v for k, v in data["scenarios"][name].items()}


# --- compiled structure of the paged decode -----------------------------------

# one attention segment of three layers, for compiles whose pools outweigh
# everything else the decode holds
STRUCT_ARCH = dataclasses.replace(TINY, n_layers=3,
                                  pattern=(Segment(("attn",), 3),))

OP_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\w+)\[([\d,]*)\]"
                     r"(?:\{[^}]*\})?\s+([\w\-]+)\(")
POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def pool_moves(hlo: str, pool_shape: tuple, depth: int) -> list[str]:
    """Instructions of a compiled program (fused computations included)
    that copy, slice or write back a whole pool: their result has one
    layer's pool shape or the stacked one."""
    shapes = {",".join(map(str, pool_shape)),
              ",".join(map(str, (depth,) + pool_shape))}
    return [line.strip() for line in hlo.splitlines()
            if (m := OP_LINE.match(line)) and m.group(3) in POOL_MOVES
            and m.group(2) in shapes]


def paged_decode_compiled(arch, *, num_blocks, block_size, slots, table_len,
                          pool_dtype=jnp.float32, sharding=None):
    """-> (compiled paged decode with the fused sampler, bytes of the whole
    cache) for ``slots`` rows; arguments are shapes on ``sharding``."""
    def sds(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)
    params = sds(jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0),
                                                  arch)))
    pools = sds(jax.eval_shape(lambda: T.init_paged_cache(
        arch, num_blocks, block_size, pool_dtype)))

    def vec(*shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    fn = ST.jit_step("paged_decode", ST.make_paged_decode_step(
        arch, sampler=make_sampler(arch.vocab)))
    compiled = fn.lower(params, pools, vec(slots, 1), vec(slots),
                        vec(slots, table_len), vec(slots),
                        vec(slots, dt=jnp.float32), vec(slots),
                        vec(slots, dt=jnp.float32),
                        vec(slots, dt=jnp.uint32)).compile()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(pools))
    return compiled, cache_bytes
