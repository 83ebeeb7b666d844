"""Architecture registry: the 10 assigned archs + the paper's own models."""
from __future__ import annotations

import dataclasses

from repro.configs.base import (ArchConfig, EncoderSpec, MLASpec, MoESpec,
                                Segment, ShapeSpec, SHAPES, SSMSpec,
                                shape_applicable)


def _load():
    from repro.configs import (arctic_480b, command_r_plus_104b,
                               deepseek_v3_671b, gemma_7b,
                               llama_3_2_vision_90b, mamba2_780m,
                               minitron_4b, qwen3_8b, whisper_medium,
                               zamba2_2_7b)
    mods = [zamba2_2_7b, arctic_480b, deepseek_v3_671b, llama_3_2_vision_90b,
            command_r_plus_104b, gemma_7b, qwen3_8b, minitron_4b,
            mamba2_780m, whisper_medium]
    return {m.ARCH.name: m.ARCH for m in mods}


ARCHS: dict[str, ArchConfig] = _load()


def get_arch(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    return ARCHS[name]


def reduce_for_smoke(arch: ArchConfig) -> ArchConfig:
    """Reduced same-family config for CPU smoke tests: small width, few
    layers/experts, tiny vocab — structure preserved.  ``cut_depth`` is
    the cut for a chip: published widths, fewer layers."""
    pattern = tuple(Segment(s.blocks, min(s.repeat, 2)) for s in arch.pattern)
    kw = dict(
        name=arch.name + "-smoke",
        d_model=128,
        n_heads=4,
        n_kv_heads=min(arch.n_kv_heads, 4) if arch.n_kv_heads < arch.n_heads else 4,
        head_dim=32 if arch.head_dim else None,
        d_ff=256 if arch.d_ff else 0,
        vocab=512,
        n_layers=sum(len(s.blocks) * min(s.repeat, 2) for s in arch.pattern),
        pattern=pattern,
        dtype="float32",
        param_dtype="float32",
        n_img_tokens=min(arch.n_img_tokens, 16),
    )
    if arch.moe:
        kw["moe"] = dataclasses.replace(
            arch.moe, n_experts=4, top_k=min(arch.moe.top_k, 2), d_ff=64,
            shared_d_ff=64 if arch.moe.n_shared_experts else 0,
            dense_d_ff=64 if arch.moe.dense_d_ff else 0, capacity_factor=2.0)
    if arch.ssm:
        kw["ssm"] = dataclasses.replace(arch.ssm, d_state=16, head_dim=16,
                                        chunk=16)
    if arch.mla:
        kw["mla"] = MLASpec(q_lora_rank=64, kv_lora_rank=32,
                            qk_nope_head_dim=32, qk_rope_head_dim=16,
                            v_head_dim=32)
    if arch.encoder:
        kw["encoder"] = EncoderSpec(n_layers=2, seq_len=24, d_ff=256)
    return dataclasses.replace(arch, **kw)


def cut_depth(arch: ArchConfig, n_layers: int) -> ArchConfig:
    """The same model with fewer layers: every width and dtype kept, each
    segment's repeat count cut by n_layers / arch.n_layers, whole periods
    only.  The name says what was cut (``qwen3-8b-8of36L``)."""
    pattern = []
    for s in arch.pattern:
        repeat, rem = divmod(s.repeat * n_layers, arch.n_layers)
        if rem or repeat < 1:
            raise ValueError(f"{arch.name}: {n_layers} of {arch.n_layers} "
                             f"layers does not cut {s} into whole periods")
        pattern.append(Segment(s.blocks, repeat))
    return dataclasses.replace(
        arch, name=f"{arch.name}-{n_layers}of{arch.n_layers}L",
        n_layers=n_layers, pattern=tuple(pattern))


__all__ = ["ARCHS", "get_arch", "reduce_for_smoke", "cut_depth", "SHAPES",
           "ShapeSpec",
           "ArchConfig", "Segment", "MoESpec", "SSMSpec", "MLASpec",
           "EncoderSpec", "shape_applicable"]
