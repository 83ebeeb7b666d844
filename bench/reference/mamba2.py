"""Mamba-2 language model (hf state-spaces/mamba2-780m; arXiv:2405.21060),
plain float32, with its loss and AdamW training steps.

Per layer: RMSNorm -> projections z, x, B, C, dt -> depthwise causal conv
and SiLU on x, B and C -> dt = softplus(dt + dt_bias), A = -exp(A_log) ->
the state-space recurrence
    y_t = sum_{s<=t} exp(sum_{r=s+1..t} dt_r A) (C_t . B_s) dt_s x_s + D x_t
computed exactly in the chunked form of the paper's minimal listing ->
y * silu(z) -> RMSNorm -> output projection, residual.  Final RMSNorm, head
tied to the embedding.  Loss: mean next-token cross-entropy.

The projections are separate matrices, as the program keeps them; the
published model fuses them into one ``in_proj``, which is the same
arithmetic.  One departure is taken from the program, so that the
comparison tests its training step and not this choice: token embeddings
are multiplied by sqrt(d_model) (PERF.md, Open questions).

The optimizer is AdamW with decoupled weight decay, gradients clipped to a
global norm, and a linear-warmup cosine schedule, as the configuration file
states them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.lowp import HIGHEST, einsum

P = "segments/0/b0/"
M = P + "mixer/"
LAYER = {
    "norm": P + "norm/scale", "z": M + "z_proj/w", "x": M + "x_proj/w",
    "B": M + "b_proj/w", "C": M + "c_proj/w", "dt": M + "dt_proj/w",
    "conv_x_w": M + "conv_x/w", "conv_x_b": M + "conv_x/b",
    "conv_B_w": M + "conv_b/w", "conv_B_b": M + "conv_b/b",
    "conv_C_w": M + "conv_c/w", "conv_C_b": M + "conv_c/b",
    "A_log": M + "A_log", "dt_bias": M + "dt_bias", "D": M + "D",
    "gate_norm": M + "norm/scale", "out": M + "out_proj/w",
}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _conv(u, w, b):
    """Depthwise causal conv and SiLU over (B, S, C) with taps w (K, C)."""
    K, S = w.shape[0], u.shape[1]
    pad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(pad[:, k: k + S] * w[k] for k in range(K)) + b)


def _segsum(a):
    """(..., T) -> (..., T, T): entry [i, j] = a[j+1] + ... + a[i] for
    j <= i, and -inf above the diagonal (masked before any exp)."""
    T = a.shape[-1]
    c = jnp.cumsum(a, -1)
    d = c[..., :, None] - c[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), d, -jnp.inf)


def _ssd(x, Bm, Cm, dt, A, chunk):
    """The state-space recurrence over whole sequences, in the chunked form
    of the Mamba-2 paper's minimal listing (ssd_minimal_discrete): exact, no
    scan.  x (b, S, H, P); Bm, Cm (b, S, N) shared by the heads; dt (b, S, H);
    A (H,)."""
    b, S, H, P = x.shape
    c = S // chunk
    if c * chunk != S:
        raise ValueError(f"sequence {S} is not a whole number of chunks "
                         f"of {chunk}")
    X = (x * dt[..., None]).reshape(b, c, chunk, H, P)
    a = jnp.moveaxis((dt * A).reshape(b, c, chunk, H), 3, 1)     # b h c l
    Bc, Cc = Bm.reshape(b, c, chunk, -1), Cm.reshape(b, c, chunk, -1)
    a_cum = jnp.cumsum(a, -1)
    L = jnp.exp(_segsum(a))                                       # b h c l s
    cb = jnp.einsum("bcln,bcsn->bcls", Cc, Bc, precision=HIGHEST)
    y_diag = jnp.einsum("bhcls,bcls,bcshp->bclhp", L, cb, X,
                        precision=HIGHEST)
    decay = jnp.exp(a_cum[..., -1:] - a_cum)                      # b h c l
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay, X,
                        precision=HIGHEST)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                        precision=HIGHEST)[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc, states, jnp.exp(a_cum),
                       precision=HIGHEST)
    return (y_diag + y_off).reshape(b, S, H, P)


def _layer(cfg, lowp, x, lw):
    mm = functools.partial(einsum, lowp=lowp)
    Bsz, S, _ = x.shape
    hd = cfg["headdim"]
    h = _rms(x, lw["norm"], cfg["norm_epsilon"])
    z = mm("bsd,de->bse", h, lw["z"])
    xs = _conv(mm("bsd,de->bse", h, lw["x"]), lw["conv_x_w"], lw["conv_x_b"])
    Bm = _conv(mm("bsd,dn->bsn", h, lw["B"]), lw["conv_B_w"], lw["conv_B_b"])
    Cm = _conv(mm("bsd,dn->bsn", h, lw["C"]), lw["conv_C_w"], lw["conv_C_b"])
    dt = jax.nn.softplus(mm("bsd,dh->bsh", h, lw["dt"]) + lw["dt_bias"])
    A = -jnp.exp(lw["A_log"])
    H = A.shape[0]
    xh = xs.reshape(Bsz, S, H, hd)
    y = _ssd(xh, Bm, Cm, dt, A, min(cfg["chunk_size"], S))
    y = (y + lw["D"][:, None] * xh).reshape(Bsz, S, H * hd)
    y = _rms(y * jax.nn.silu(z), lw["gate_norm"], cfg["norm_epsilon"])
    return x + mm("bse,ed->bsd", y, lw["out"])


def loss(w: dict, cfg: dict, tokens, labels, lowp: bool = False):
    d, V = cfg["d_model"], cfg["vocab_size"]
    x = w["embed"][tokens] * math.sqrt(d)
    body = jax.checkpoint(functools.partial(_layer, cfg, lowp))
    x, _ = jax.lax.scan(lambda c, lw: (body(c, lw), None), x, w["layers"])
    x = _rms(x, w["final_norm"], cfg["norm_epsilon"])
    logits = einsum("bsd,vd->bsv", x, w["embed"][:V], lowp=lowp)
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - tgt)


def weights(named: dict) -> dict:
    """The program's parameter paths -> this module's names, in float32."""
    f = functools.partial(jnp.asarray, dtype=jnp.float32)
    return {"embed": f(named["embed/embedding"]),
            "final_norm": f(named["final_norm/scale"]),
            "layers": {k: f(named[p]) for k, p in LAYER.items()}}


def lr_at(opt: dict, step: int) -> float:
    """Linear warmup to ``lr``, then cosine down to ``min_lr_ratio``."""
    peak, warm = opt["lr"], opt["warmup_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    prog = min(max((step - warm) / max(opt["total_steps"] - warm, 1), 0.0),
               1.0)
    r = opt["min_lr_ratio"]
    return peak * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * prog)))


def _cfg_key(cfg):
    keys = ("d_model", "vocab_size", "headdim", "norm_epsilon", "chunk_size")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("cfg_items", "lowp"))
def _grads(w, tokens, labels, cfg_items, lowp):
    return jax.value_and_grad(loss)(w, dict(cfg_items), tokens, labels, lowp)


@jax.jit
def _adamw(w, g, m, v, lr, t, b1, b2, eps, wd, clip):
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (norm + 1e-9)), g)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    w = jax.tree.map(
        lambda w, m, v: w - lr * ((m / (1 - b1 ** t))
                                  / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                                  + wd * w), w, m, v)
    return w, g, m, v


def train(w: dict, cfg: dict, opt: dict, batches, lowp: bool = False,
          grad_rows=None, block=None):
    """AdamW steps over ``batches`` [(tokens, labels), ...] from ``w``.

    -> (losses, first clipped gradient, weights after the last step).
    The gradient of the mean loss is summed over blocks of ``block`` rows,
    each weighted by its share of the rows, so that activations fit.
    ``grad_rows`` keeps only the first rows of each batch (a planted fault:
    the mean taken over part of the batch)."""
    cfg_items = _cfg_key(cfg)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, g1 = [], None
    for t, (tok, lab) in enumerate(batches, start=1):
        if grad_rows is not None:
            tok, lab = tok[:grad_rows], lab[:grad_rows]
        rows = tok.shape[0]
        step = block or rows
        val, g = 0.0, None
        for i in range(0, rows, step):
            f = min(step, rows - i) / rows
            bv, bg = _grads(w, tok[i:i + step], lab[i:i + step], cfg_items,
                            lowp)
            val += f * float(bv)
            g = jax.tree.map(lambda x: f * x, bg) if g is None else \
                jax.tree.map(lambda a, x: a + f * x, g, bg)
        w, g, m, v = _adamw(w, g, m, v, lr_at(opt, t), float(t), opt["b1"],
                            opt["b2"], opt["eps"], opt["weight_decay"],
                            opt["clip_norm"])
        losses.append(val)
        if g1 is None:
            g1 = g
    return losses, g1, w


def by_path(tree: dict) -> dict:
    """This module's names -> the program's parameter paths (the inverse of
    ``weights``)."""
    out = {"embed/embedding": tree["embed"],
           "final_norm/scale": tree["final_norm"]}
    out.update({p: tree["layers"][k] for k, p in LAYER.items()})
    return out
