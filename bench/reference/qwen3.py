"""Qwen3 decoder forward (hf Qwen/Qwen3-8B), plain float32.

Per layer: RMSNorm -> q/k/v projections -> per-head RMSNorm of q and k ->
rotary embedding (rotate-half form, ``rope_theta``) -> causal grouped-query
attention (query head h reads key/value head h // (heads / kv heads)) ->
output projection, residual; RMSNorm -> SwiGLU MLP, residual.  Final RMSNorm,
untied head.

One departure from the published model, taken from the program so that the
comparison tests its serving path and not this choice: token embeddings are
multiplied by sqrt(hidden_size) (PERF.md, Open questions).

``gaps`` serves the correctness check: over one sequence (prompt and the
tokens the engine served), how far each served token's logit lies below the
reference's best at that position.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.lowp import einsum

P = "segments/0/b0/"
LAYER = {
    "in_norm": P + "norm1/scale", "q": P + "attn/wq/w", "k": P + "attn/wk/w",
    "v": P + "attn/wv/w", "o": P + "attn/wo/w", "q_norm": P + "attn/q_norm/scale",
    "k_norm": P + "attn/k_norm/scale", "post_norm": P + "norm2/scale",
    "up": P + "mlp/w_in/w", "gate": P + "mlp/w_gate/w", "down": P + "mlp/w_out/w",
}
Q_BLOCK = 512          # query rows of attention per block
ROW_BLOCK = 512        # rows of the head per block


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, heads, D): rotate the two halves of each head."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(w: dict, cfg: dict, tokens, lowp: bool = False):
    """Final-normed hidden states (S, hidden) of one sequence."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Hkv, D = cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = w["embed"][tokens].astype(jnp.float32) * math.sqrt(d)
    mm = functools.partial(einsum, lowp=lowp)

    def layer(x, lw):
        h = _rms(x, lw["in_norm"], eps)
        q = mm("sd,de->se", h, lw["q"]).reshape(S, H, D)
        k = mm("sd,de->se", h, lw["k"]).reshape(S, Hkv, D)
        v = mm("sd,de->se", h, lw["v"]).reshape(S, Hkv, D)
        q = _rope(_rms(q, lw["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, lw["k_norm"], eps), pos, theta)
        k, v = jnp.repeat(k, H // Hkv, 1), jnp.repeat(v, H // Hkv, 1)

        def block(qs):
            qb, pb = qs                                    # (Bq, H, D), (Bq,)
            lg = mm("qhd,thd->hqt", qb, k) / math.sqrt(D)
            lg = jnp.where(pb[None, :, None] >= pos[None, None, :], lg,
                           -jnp.inf)
            return mm("hqt,thd->qhd", jax.nn.softmax(lg, -1), v)

        qb = min(Q_BLOCK, S)
        o = jax.lax.map(block, (q.reshape(S // qb, qb, H, D),
                                pos.reshape(S // qb, qb)))
        x = x + mm("se,ed->sd", o.reshape(S, H * D), lw["o"])
        h = _rms(x, lw["post_norm"], eps)
        g = jax.nn.silu(mm("sd,df->sf", h, lw["gate"])) \
            * mm("sd,df->sf", h, lw["up"])
        return x + mm("sf,fd->sd", g, lw["down"]), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, w["final_norm"], eps)


def weights(named: dict) -> dict:
    """The program's parameter paths -> this module's names."""
    return {"embed": named["embed/embedding"],
            "final_norm": named["final_norm/scale"],
            "head": named["head/w"],
            "layers": {k: named[p] for k, p in LAYER.items()}}


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _gaps(w, tokens, cfg_items, control):
    cfg = dict(cfg_items)
    V = cfg["vocab_size"]
    h = hidden(w, cfg, tokens)
    hc = hidden(w, cfg, tokens, lowp=True) if control else h
    S = tokens.shape[0]
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])

    def block(xs):
        hb, hcb, tb = xs
        ref = einsum("sd,dv->sv", hb, w["head"][:, :V])
        best = jnp.max(ref, -1)
        if control:
            pick = jnp.argmax(einsum("sd,dv->sv", hcb, w["head"][:, :V],
                                     lowp=True), -1)
        else:
            pick = tb
        return best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]

    rb = min(ROW_BLOCK, S)
    return jax.lax.map(block, (h.reshape(S // rb, rb, -1),
                               hc.reshape(S // rb, rb, -1),
                               nxt.reshape(S // rb, rb))).reshape(S)


def gaps(w: dict, cfg: dict, seq, n_prompt: int, pad_to: int,
         control: bool = False):
    """Logit gaps, in nats, at the positions that chose a served token.

    ``seq`` is the prompt followed by the served tokens.  Entry i is the
    reference's best logit less the reference's logit of served token i
    (``control=False``), or of the token that the float8 control ranks
    first at that position (``control=True``).  The sequence is padded to
    ``pad_to`` tokens, so that every sequence runs one compiled program."""
    n = len(seq)
    toks = np.zeros(pad_to, np.int32)
    toks[:n] = seq
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "vocab_size")
    out = _gaps(w, jnp.asarray(toks), tuple((k, cfg[k]) for k in keys),
                control)
    return np.asarray(out)[n_prompt - 1: n - 1]
