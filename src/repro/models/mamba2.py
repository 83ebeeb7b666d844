"""Mamba2 block — SSD (state-space duality) form, arXiv:2405.21060.

Training/prefill uses the chunked SSD algorithm (intra-chunk quadratic term
that maps onto the MXU + inter-chunk linear recurrence); decode is the O(1)
recurrent update carrying ``(conv_state, ssm_state)``.  The chunked form here
is also the oracle for ``kernels/ssd_scan.py``.

TPU-native sharding note (DESIGN.md §2): projections are kept *separate*
(z/x/B/C/dt + per-stream causal convs) instead of the reference fused
``in_proj``: the fused layout slices a concatenated output dim at boundaries
that do not align with a 16-way `model` shard, forcing GSPMD reshards.  With
separate weights, x/z/dt shard by SSM head over `model`, B/C stay replicated
(they are per-group and tiny), and every SSD einsum keeps the head axis
sharded end-to-end.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L

Params = dict
Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    d_model: int
    d_state: int = 64
    head_dim: int = 64
    expand: int = 2
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 128

    @property
    def d_inner(self):
        return self.expand * self.d_model

    @property
    def n_heads(self):
        return self.d_inner // self.head_dim

    @property
    def d_bc(self):
        return self.n_groups * self.d_state


def init_mamba2(key, cfg: Mamba2Config, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 8)
    H = cfg.n_heads
    dt = jnp.exp(jax.random.uniform(ks[6], (H,)) *
                 (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    conv_scale = 1.0 / math.sqrt(cfg.d_conv)
    return {
        "z_proj": L.init_dense(ks[0], cfg.d_model, cfg.d_inner, dtype=dtype),
        "x_proj": L.init_dense(ks[1], cfg.d_model, cfg.d_inner, dtype=dtype),
        "b_proj": L.init_dense(ks[2], cfg.d_model, cfg.d_bc, dtype=dtype),
        "c_proj": L.init_dense(ks[3], cfg.d_model, cfg.d_bc, dtype=dtype),
        "dt_proj": L.init_dense(ks[4], cfg.d_model, H, dtype=dtype),
        "conv_x": {"w": L._normal(ks[5], (cfg.d_conv, cfg.d_inner), dtype, conv_scale),
                   "b": jnp.zeros((cfg.d_inner,), dtype)},
        "conv_b": {"w": L._normal(jax.random.fold_in(ks[5], 1),
                                  (cfg.d_conv, cfg.d_bc), dtype, conv_scale),
                   "b": jnp.zeros((cfg.d_bc,), dtype)},
        "conv_c": {"w": L._normal(jax.random.fold_in(ks[5], 2),
                                  (cfg.d_conv, cfg.d_bc), dtype, conv_scale),
                   "b": jnp.zeros((cfg.d_bc,), dtype)},
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "norm": L.init_rmsnorm(cfg.d_inner, dtype),
        "out_proj": L.init_dense(ks[7], cfg.d_inner, cfg.d_model, dtype=dtype,
                                 scale=1.0 / math.sqrt(cfg.d_inner)),
    }


def init_mamba2_cache(cfg: Mamba2Config, batch: int, dtype=jnp.float32) -> Params:
    K = cfg.d_conv - 1
    return {
        "conv_x": jnp.zeros((batch, K, cfg.d_inner), dtype),
        "conv_b": jnp.zeros((batch, K, cfg.d_bc), dtype),
        "conv_c": jnp.zeros((batch, K, cfg.d_bc), dtype),
        "ssm": jnp.zeros((batch, cfg.n_heads, cfg.head_dim, cfg.d_state), dtype),
    }


def _causal_conv(u: Array, conv: Params,
                 left: Optional[Array] = None) -> Array:
    """Depthwise causal conv1d + silu. u: (B,S,C); w: (K,C).

    ``left`` (B, K-1, C) supplies the raw inputs *preceding* u — the carried
    conv buffer during chunked prefill.  None means start-of-sequence
    (zero left context, identical to the old zero-padding)."""
    w = conv["w"]
    K = w.shape[0]
    if left is None:
        pad = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        pad = jnp.concatenate([left.astype(u.dtype), u], axis=1)
    out = sum(pad[:, k: k + u.shape[1], :] * w[k].astype(u.dtype) for k in range(K))
    return jax.nn.silu(out + conv["b"].astype(u.dtype))


def _conv_tail(buf: Array, raw: Array,
               new_lens: Optional[Array] = None) -> Array:
    """Next conv buffer: last (d_conv-1) valid raw inputs of buffer+chunk.

    buf: (B, K, C) carried buffer; raw: (B, S, C) this chunk's raw conv
    inputs; new_lens (B,) marks rows >= new_lens[b] as padding to skip.
    Always yields K rows even when the valid chunk is shorter than K (the
    old buffer supplies the missing left context)."""
    K = buf.shape[1]
    full = jnp.concatenate([buf, raw.astype(buf.dtype)], axis=1)  # (B,K+S,C)
    if new_lens is None:
        return full[:, -K:, :]
    idx = (new_lens[:, None] + jnp.arange(K))[:, :, None]         # (B,K,1)
    return jnp.take_along_axis(full, idx, axis=1)


def _conv_step(u_new: Array, buf: Array, conv: Params) -> tuple[Array, Array]:
    """One-token conv update. u_new: (B,1,C); buf: (B,K-1,C)."""
    w = conv["w"]
    full = jnp.concatenate([buf, u_new.astype(buf.dtype)], axis=1)  # (B,K,C)
    out = sum(full[:, k, :] * w[k].astype(buf.dtype) for k in range(w.shape[0]))
    out = jax.nn.silu(out + conv["b"].astype(buf.dtype))
    return out[:, None, :], full[:, 1:, :]


def _ssd_chunked(cfg: Mamba2Config, x, Bm, Cm, dt_a, h0=None):
    """Chunked SSD scan (pure jnp oracle).

    x: (B,S,H,P); Bm,Cm: (B,S,G,N); dt_a = (dt (B,S,H), a (B,S,H)).
    Returns (y (B,S,H,P) fp32, h_final (B,H,P,N) fp32).
    """
    dt, a = dt_a
    Bsz, S_orig, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(cfg.chunk, S_orig)
    if S_orig % Q:  # pad: dt=0, a=0 => decay 1, zero input — state unaffected
        pad = Q - S_orig % Q
        def padf(t):
            return jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        x, Bm, Cm, dt, a = map(padf, (x, Bm, Cm, dt, a))
    S = x.shape[1]
    nc = S // Q
    hpg = H // G

    def rc(t, extra):  # reshape into chunks, chunk axis leading (scan xs)
        return jnp.moveaxis(t.reshape((Bsz, nc, Q) + extra), 1, 0)

    xs_ = (rc(x.astype(jnp.float32), (H, P)),
           rc(Bm.astype(jnp.float32), (G, N)),
           rc(Cm.astype(jnp.float32), (G, N)),
           rc(dt, (H,)), rc(a, (H,)))
    head_group = jnp.arange(H) // hpg
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def body(h, inp):
        """One chunk: intra-chunk quadratic term + carried recurrent state.
        Peak temp is (B,Q,Q,H) for a single chunk — the scan keeps the whole
        sequence's decay tensors from materializing at once."""
        x_c, B_c, C_c, dt_c, a_c = inp                 # (B,Q,...)
        cum = jnp.cumsum(a_c, axis=1)                  # (B,Q,H)
        seg = cum[:, :, None, :] - cum[:, None, :, :]  # (B,Q,Q,H)
        # mask before exp: above the diagonal seg is a positive sum of
        # -a that overflows exp at real decay rates, and the backward of a
        # mask applied after exp is then 0 * inf = nan
        decay = jnp.exp(jnp.where(tri[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("bign,bjgn->bijg", C_c, B_c)   # (B,Q,Q,G)
        cb = jnp.repeat(cb, hpg, axis=-1)              # g -> h
        scores = cb * decay * dt_c[:, None, :, :]
        y = jnp.einsum("bijh,bjhp->bihp", scores, x_c)
        # inter-chunk: contribution of the carried state
        Ch = C_c[:, :, head_group, :]                  # (B,Q,H,N)
        y = y + jnp.einsum("bqhn,bhpn->bqhp", Ch, h) * jnp.exp(cum)[..., None]
        # state update: h' = decay_chunk * h + sum_j exp(cum_end-cum_j) dt_j B_j x_j
        Bh = B_c[:, :, head_group, :]
        dec_end = jnp.exp(cum[:, -1:, :] - cum)        # (B,Q,H)
        bx = jnp.einsum("bqh,bqhp,bqhn->bhpn", dec_end * dt_c, x_c, Bh)
        h = h * jnp.exp(cum[:, -1, :])[:, :, None, None] + bx
        return h, y

    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    h_final, ys = jax.lax.scan(body, h0.astype(jnp.float32), xs_)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, S, H, P)
    return y[:, :S_orig], h_final


def mamba2(p: Params, cfg: Mamba2Config, x: Array, *,
           cache: Optional[Params] = None,
           new_lens: Optional[Array] = None,
           impl: str = "xla") -> tuple[Array, Optional[Params]]:
    """x: (B,S,D).  With ``cache`` and S==1 runs the recurrent decode path.

    With ``cache`` and S>1 (prefill) the cached conv buffers supply the raw
    left context and the cached SSM state seeds the scan (h0), so a prompt
    may be fed in several chunks and the handoff state is exact at every
    chunk boundary.  ``new_lens`` (B,) marks token rows >= new_lens[b] as
    padding: their dt is zeroed (decay 1, zero input — state untouched) and
    they never enter the carried conv buffer, so fixed-shape prompt chunks
    trace once (see layers.paged_attention for the attention analogue)."""
    Bsz, S, D = x.shape
    H, P, G, N = cfg.n_heads, cfg.head_dim, cfg.n_groups, cfg.d_state
    z = L.dense(p["z_proj"], x)
    xr = L.dense(p["x_proj"], x)
    br = L.dense(p["b_proj"], x)
    cr = L.dense(p["c_proj"], x)
    dt_raw = L.dense(p["dt_proj"], x)
    A = -jnp.exp(p["A_log"])                                       # (H,)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    head_group = jnp.arange(H) // (H // G)

    if cache is not None and S == 1:
        xu, conv_x = _conv_step(xr, cache["conv_x"], p["conv_x"])
        bu, conv_b = _conv_step(br, cache["conv_b"], p["conv_b"])
        cu, conv_c = _conv_step(cr, cache["conv_c"], p["conv_c"])
        xs = xu.reshape(Bsz, H, P).astype(jnp.float32)
        Bm = bu.reshape(Bsz, G, N).astype(jnp.float32)
        Cm = cu.reshape(Bsz, G, N).astype(jnp.float32)
        a = jnp.exp(dt[:, 0] * A[None, :])                         # (B,H)
        Bh, Chd = Bm[:, head_group, :], Cm[:, head_group, :]       # (B,H,N)
        h = (cache["ssm"].astype(jnp.float32) * a[:, :, None, None]
             + jnp.einsum("bh,bhp,bhn->bhpn", dt[:, 0], xs, Bh))
        y = jnp.einsum("bhpn,bhn->bhp", h, Chd)
        y = y + p["D"][None, :, None] * xs
        y = y.reshape(Bsz, 1, cfg.d_inner)
        new_cache = {"conv_x": conv_x, "conv_b": conv_b, "conv_c": conv_c,
                     "ssm": h.astype(cache["ssm"].dtype)}
    else:
        left_x = cache["conv_x"] if cache is not None else None
        left_b = cache["conv_b"] if cache is not None else None
        left_c = cache["conv_c"] if cache is not None else None
        xc = _causal_conv(xr, p["conv_x"], left=left_x)
        bc = _causal_conv(br, p["conv_b"], left=left_b)
        cc = _causal_conv(cr, p["conv_c"], left=left_c)
        xs = xc.reshape(Bsz, S, H, P)
        Bm = bc.reshape(Bsz, S, G, N)
        Cm = cc.reshape(Bsz, S, G, N)
        if new_lens is not None:
            # padded tail rows: dt=0 => decay 1, zero input — state untouched
            valid = jnp.arange(S)[None, :] < new_lens[:, None]     # (B,S)
            dt = jnp.where(valid[:, :, None], dt, 0.0)
        a = dt * A[None, None, :]                                  # (B,S,H)
        h0 = cache["ssm"] if cache is not None else None
        if impl == "pallas":
            from repro.kernels import ops as kops
            y, h_final = kops.ssd_scan(cfg, xs, Bm, Cm, dt, a, h0=h0)
        else:
            y, h_final = _ssd_chunked(cfg, xs, Bm, Cm, (dt, a), h0=h0)
        y = y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
        y = y.reshape(Bsz, S, cfg.d_inner)
        if cache is not None:
            # prefill -> decode handoff: the next conv buffer is the last
            # (d_conv-1) *valid* raw inputs of buffer+chunk — prepending the
            # old buffer left-pads prompts shorter than d_conv-1 with the
            # carried (initially zero) context instead of under-filling
            new_cache = {
                "conv_x": _conv_tail(left_x, xr, new_lens),
                "conv_b": _conv_tail(left_b, br, new_lens),
                "conv_c": _conv_tail(left_c, cr, new_lens),
                "ssm": h_final.astype(cache["ssm"].dtype),
            }
        else:
            new_cache = None

    y = y.astype(x.dtype) * jax.nn.silu(z)
    y = L.rmsnorm(p["norm"], y)
    return L.dense(p["out_proj"], y), new_cache


def mamba2_slot(p: Params, cfg: Mamba2Config, x: Array, *,
                pool: Params, slot_ids: Array,
                new_lens: Optional[Array] = None,
                layer: Optional[Array] = None,
                impl: str = "xla") -> tuple[Array, Params]:
    """Serving path over a *slot-indexed state pool* (continuous batching).

    pool: the mamba2 cache tree with a leading (slots+1) row axis shared by
    all in-flight requests — row i holds engine slot i's recurrent state and
    the last row is the reserved null slot (the slot-state analogue of the
    paged-KV null block).  ``slot_ids`` (B,) maps each batch row to its pool
    row; inactive batch rows point at the null slot, so their garbage
    updates scatter into scratch that no live request ever reads.  With
    ``layer`` the pool is the stacked (L, slots+1, ...) scan carry and the
    rows are those of layer ``layer`` (layers.layer_index).

    Gather rows -> run :func:`mamba2`'s recurrence/chunked scan on them
    (decode when S==1 and new_lens is None, chunk-prefill otherwise, with
    the SSM state carried as h0 across chunks) -> scatter updated rows back.
    """
    at = L.layer_index(layer, slot_ids)
    rows = jax.tree.map(lambda t: t[at], pool)
    decode = x.shape[1] == 1 and new_lens is None
    y, new_rows = mamba2(p, cfg, x, cache=rows,
                         new_lens=None if decode else new_lens, impl=impl)
    new_pool = jax.tree.map(
        lambda t, n: t.at[at].set(n.astype(t.dtype)), pool, new_rows)
    return y, new_pool
