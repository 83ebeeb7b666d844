"""Assignment -> GSPMD sharding translation (DESIGN.md §2 table).

Per-leaf PartitionSpecs are derived from the parameter tree *path* (module
and leaf names fixed by the model substrate), the component's assigned
Strategy, and divisibility of the dims by the mesh axes.

Fallback rule: any dim that an axis does not divide is replicated instead —
JAX rejects uneven shardings (verified), and head-count-dependent reshapes
(e.g. arctic 56 heads, minitron 24 heads vs model=16) would force GSPMD
reshards.  Such attention mixers keep replicated weights under MP and shard
only over `data` (ZeRO-style) under HP; their FFN halves shard fully.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.components import SPLIT_KEYS, abstract_params
from repro.core.costmodel import MeshShape
from repro.core.strategy import Strategy

# EP layout for MoE expert stacks: "model" (baseline: experts over `model`,
# expert-tensor over `data` under HP) or "data" (optimized EP-major: experts
# over `data`, expert-FF over `model`; pairs with moe.EP_CONSTRAINTS)
MOE_EP_AXIS = "model"

# column-parallel modules (shard d_out over `model`); row-parallel (d_in)
COL = {"wq", "wk", "wv", "w_in", "w_gate", "z_proj", "x_proj", "dt_proj",
       "wq_a", "wq_b", "wk_b", "wv_b"}
ROW = {"wo", "w_out", "out_proj"}
# always-replicated small weights (see module docstring / mamba2.py note)
REPL = {"b_proj", "c_proj", "wkv_a", "router", "conv_b", "conv_c",
        "q_norm", "k_norm", "kv_norm", "norm", "norm1", "norm2", "norm3",
        "final_norm", "gate", "mlp_gate", "dt_bias", "cls", "pos"}


def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0


def _q_heads_ok(arch: ArchConfig, mesh: MeshShape) -> bool:
    """wq/wo shard iff the (B,S,q_dim@model)->(B,S,H,hd) reshape stays
    sharded, i.e. n_heads % model == 0 (else: arctic 56H, minitron 24H)."""
    return _div(arch.n_heads, mesh.model)


def _kv_heads_ok(arch: ArchConfig, mesh: MeshShape) -> bool:
    """wk/wv shard iff n_kv_heads % model == 0.  When false they stay
    replicated (tiny: D x kv_dim).  Attention (layers._sdpa,
    layers._paged_sdpa) reshapes q's head axis to (Hkv, G) and never copies
    k/v to H heads; the kv-head axis lines up with the pool's when that
    axis shards."""
    return _div(min(arch.n_kv_heads, arch.n_heads), mesh.model)


def _sanitize(spec: P, shape: tuple, mesh: MeshShape) -> P:
    """Replicate any dim an axis doesn't divide (safety net)."""
    sizes = {"data": mesh.data, "model": mesh.model, "pod": mesh.pod}
    out = []
    for i, ax in enumerate(spec):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        total = 1
        for a in axes:
            total *= sizes[a]
        out.append(ax if i < len(shape) and _div(shape[i], total) else None)
    return P(*out)


def leaf_spec(names: tuple, shape: tuple, strat: Strategy,
              mesh: MeshShape, arch: ArchConfig) -> P:
    """Spec for an UNSTACKED leaf (stack prefix added by caller)."""
    rank = len(shape)
    mod = names[-2] if len(names) >= 2 else names[-1]
    leaf = names[-1]
    in_moe = "moe" in names
    shared_blk = "shared" in names

    if strat == Strategy.DP:
        return P(*([None] * rank))
    if strat == Strategy.FS:
        # FS weight layout == HP's 2-axis sharding; the difference is the
        # batch/activation layout (over ALL axes), set by the launcher.
        strat = Strategy.HP

    hp = strat == Strategy.HP
    # HP shards the ZeRO dim over pod too (multi-pod: params /512 not /256)
    data_ax = ("data", "pod") if (hp and mesh.pod > 1) else "data"

    # ---- embedding / head -------------------------------------------------
    if leaf == "embedding":
        return P("model", data_ax if hp else None)
    if "head" in names and leaf == "w":
        return P(data_ax if hp else None, "model")
    if "head" in names and leaf == "b":
        return P("model")

    # ---- MoE expert-stacked arrays (E, D, F) / (E, F, D) ------------------
    if in_moe and leaf in ("w_in", "w_gate", "w_out") and rank == 3:
        if MOE_EP_AXIS == "data":
            # EP-major: experts over `data`, expert-FF dim over `model`
            # (w_in/w_gate: (E,D,F) -> F; w_out: (E,F,D) -> F is dim 1)
            return (P("data", None, "model") if leaf in ("w_in", "w_gate")
                    else P("data", "model", None))
        return P("model", data_ax if hp else None, None)

    # ---- norms / replicated -----------------------------------------------
    if mod in REPL or leaf in REPL:
        # mamba2's gated rmsnorm scale lives on the head-sharded d_inner
        if mod == "norm" and "mixer" in names and arch.ssm is not None:
            return P("model")
        return P(*([None] * rank))

    # ---- attention q/k/v/o with head-divisibility gating -------------------
    if mod in ("wq", "wk", "wv", "wo") and not in_moe:
        if shared_blk:                         # zamba2 shared block: full MHA
            ok = _div(arch.n_heads, mesh.model)
        elif mod in ("wk", "wv"):
            ok = _kv_heads_ok(arch, mesh)
        else:
            ok = _q_heads_ok(arch, mesh)
        if not ok:
            # fallback: ZeRO-only sharding under HP, replicate under MP
            if hp and leaf == "w":
                return P(data_ax, None)
            return P(*([None] * rank))

    # mamba2 head-sharded projections need H % model == 0
    if mod in ("z_proj", "x_proj", "dt_proj", "out_proj") and arch.ssm is not None:
        H = (arch.ssm.expand * arch.d_model) // arch.ssm.head_dim
        if not _div(H, mesh.model):
            if hp:
                return P(data_ax, None) if leaf == "w" else P(None)
            return P(*([None] * rank))

    if mod == "conv_x" or (mod in ("conv_x",) and leaf in ("w", "b")):
        return P(None, "model") if leaf == "w" else P("model")
    if leaf in ("A_log", "D") and rank == 1:
        return P("model")

    if mod in COL:
        if leaf == "w":
            return P(data_ax if hp else None, "model")
        return P("model")           # bias on the sharded output dim
    if mod in ROW:
        if leaf == "w":
            return P("model", data_ax if hp else None)
        return P(*([None] * rank))  # bias after the all-reduce: replicated

    if mod == "app_proj":           # zamba2 per-application out projection
        if leaf == "w":
            return P("model", data_ax if hp else None)
        return P(*([None] * rank))
    if mod == "proj":               # mtp concat projection
        return P(None, "model") if leaf == "w" else P("model")

    return P(*([None] * rank))


# ---------------------------------------------------------------------------
# component lookup
# ---------------------------------------------------------------------------

def _names_of(path) -> tuple:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(k.key)
        elif hasattr(k, "idx"):
            out.append(k.idx)
        elif hasattr(k, "name"):
            out.append(k.name)
    return tuple(out)


def component_name_of(names: tuple, arch: ArchConfig) -> Optional[str]:
    if names[0] == "embed":
        return "embed"
    if names[0] == "head":
        return "head"
    if names[0] == "mtp":
        return "mtp"
    if names[0] == "encoder":
        return "encoder"
    if names[0] == "final_norm":
        return None
    if names[0] == "shared":
        for si, seg in enumerate(arch.pattern):
            for bi, kind in enumerate(seg.blocks):
                if kind == "shared_attn":
                    return f"seg{si}/b{bi}:shared_attn"
        return None
    if names[0] == "segments":
        si, b = names[1], names[2]
        bi = int(b[1:])
        kind = arch.pattern[si].blocks[bi]
        if kind in SPLIT_KEYS:
            mixer_keys, _ = SPLIT_KEYS[kind]
            sub = "mixer" if names[3] in mixer_keys else "ffn"
            return f"seg{si}/b{bi}:{kind}.{sub}"
        return f"seg{si}/b{bi}:{kind}"
    return None


def _stack_depth(names: tuple) -> int:
    return 1 if names[0] == "segments" or \
        (names[0] == "encoder" and len(names) > 1 and names[1] == "segments") else 0


# ---------------------------------------------------------------------------
# public builders
# ---------------------------------------------------------------------------

def param_specs(arch: ArchConfig, assignment: dict[str, Strategy],
                mesh: MeshShape):
    """PartitionSpec tree mirroring init_lm's params exactly."""
    aparams = abstract_params(arch)

    def rule(path, leaf):
        names = _names_of(path)
        comp = component_name_of(names, arch)
        strat = assignment.get(comp, Strategy.DP) if comp else Strategy.DP
        depth = _stack_depth(names)
        spec = leaf_spec(tuple(n for n in names if isinstance(n, str)),
                         leaf.shape[depth:], strat, mesh, arch)
        full = P(*([None] * depth + list(spec)))
        return _sanitize(full, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(rule, aparams)


def batch_axes(mesh: MeshShape, batch: int, *, full: bool = False):
    """Largest batch sharding the mesh allows for this batch size.
    full=True (FS / uniform-DP): batch over every axis when divisible."""
    if full:
        axes = tuple(a for a, n in (("pod", mesh.pod), ("data", mesh.data),
                                    ("model", mesh.model)) if n > 1)
        total = mesh.chips
        if axes and _div(batch, total):
            return axes
    if mesh.pod > 1 and _div(batch, mesh.pod * mesh.data):
        return ("pod", "data")
    if _div(batch, mesh.data):
        return "data"
    return None


def token_spec(mesh: MeshShape, batch: int, *, full: bool = False) -> P:
    return P(batch_axes(mesh, batch, full=full), None)


def opt_state_specs(opt_sds, param_specs_tree, mesh: MeshShape):
    """Specs for an OptState pytree.

    fp32 moments mirror the param specs (ZeRO follows the HP params for
    free).  Int8 QLeaf moments are flat (n_blocks, 256) — shard dim0 over
    every mesh axis that divides it (fully-sharded optimizer state).
    """
    from repro.optim.quantized import QLeaf

    def flat_rule(leaf):
        n = leaf.shape[0]
        for axes in ((("data", "model", "pod") if mesh.pod > 1
                      else ("data", "model")),
                     ("data", "model"), ("data",), None):
            if axes is None:
                return P(*([None] * len(leaf.shape)))
            total = 1
            sizes = {"data": mesh.data, "model": mesh.model, "pod": mesh.pod}
            for a in axes:
                total *= sizes[a]
            if _div(n, total):
                return P(axes, *([None] * (len(leaf.shape) - 1)))

    def moment_specs(m_sds):
        has_q = any(isinstance(x, QLeaf)
                    for x in jax.tree.leaves(
                        m_sds, is_leaf=lambda t: isinstance(t, QLeaf)))
        if has_q:
            return jax.tree.map(flat_rule, m_sds)
        return param_specs_tree

    step, mu, nu, extra = opt_sds
    return type(opt_sds)(P(), moment_specs(mu), moment_specs(nu),
                         None if extra is None else jax.tree.map(flat_rule, extra))


def paged_cache_specs(arch: ArchConfig, assignment: dict[str, Strategy],
                      mesh: MeshShape):
    """Spec tree mirroring init_paged_cache: per-segment stacked pools for
    both serving state classes.

    attn-family block pools are (repeat, num_blocks, block_size, Hkv,
    head_dim).  They have no batch axis and their block axis is gathered
    through block tables every step, so neither can carry the MP shard;
    instead the kv-head axis shards over `model` (the classic paged-KV
    layout) whenever the head count divides, else the pool is replicated.

    Slot-state pools have a leading (repeat, slots+1) prefix.  mamba2 state
    shards its SSM head axis over `model` under MP/HP when the head count
    divides; cross-attn K/V shards its kv-head axis like the attn pools.

    zamba2's shared block pages a full-MHA pool per application (head axis
    over `model` when n_heads divides); whisper's wdec carries a paged
    self-attn pool plus a slot-state encoder-K/V pool; MLA's latent
    (c_kv, k_rope) pools are replicated — the rank axis is contracted inside
    the absorbed-score einsums and is tiny by design (the point of MLA).

    Specs are emitted in GSPMD's *canonical* form (trailing Nones stripped,
    fully-replicated as P()): the pools are device_put with these specs at
    engine init and then flow through the jitted steps, whose output
    shardings come back canonicalized — a non-canonical initial spec hashes
    differently and silently retraces every step on its second call
    (caught by the tracecheck trace-cache analyzer)."""
    def _canon(spec):
        parts = tuple(spec)
        while parts and parts[-1] is None:
            parts = parts[:-1]
        return P(*parts)

    specs = []
    for si, seg in enumerate(arch.pattern):
        seg_spec = {}
        for bi, kind in enumerate(seg.blocks):
            if kind not in ("attn", "moe_attn", "mamba2", "cross_attn",
                            "mla", "mla_dense", "shared_attn", "wdec"):
                raise ValueError(
                    f"paged/slot-state cache unsupported for block kind "
                    f"{kind!r}")
            comp = f"seg{si}/b{bi}:{kind}.mixer" if kind in SPLIT_KEYS \
                else f"seg{si}/b{bi}:{kind}"
            strat = assignment.get(comp, Strategy.DP)
            mp = strat in (Strategy.MP, Strategy.HP)
            if kind == "mamba2":
                H = (arch.ssm.expand * arch.d_model) // arch.ssm.head_dim
                h_ax = "model" if (mp and _div(H, mesh.model)) else None
                seg_spec[f"b{bi}"] = {
                    "conv_x": P(None, None, None, h_ax),
                    "conv_b": P(None, None, None, None),
                    "conv_c": P(None, None, None, None),
                    "ssm": P(None, None, h_ax, None, None)}
                continue
            if kind in ("mla", "mla_dense"):
                seg_spec[f"b{bi}"] = {"c_kv": P(None, None, None, None),
                                      "k_rope": P(None, None, None, None)}
                continue
            if kind == "shared_attn":
                h_ax = "model" if (mp and _div(arch.n_heads, mesh.model)) \
                    else None
                pool = P(None, None, None, h_ax, None)
                seg_spec[f"b{bi}"] = {"k": pool, "v": pool}
                continue
            h_ax = "model" if (mp and _kv_heads_ok(arch, mesh)) else None
            pool = P(None, None, None, h_ax, None)
            if kind == "wdec":
                seg_spec[f"b{bi}"] = {"self": {"k": pool, "v": pool},
                                      "cross": {"k": pool, "v": pool}}
                continue
            seg_spec[f"b{bi}"] = {"k": pool, "v": pool}
        specs.append(seg_spec)
    return jax.tree.map(_canon, specs)
