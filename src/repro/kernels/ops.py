"""Jit'd wrappers exposing the Pallas kernels with model-layer layouts.

The model passes (B, S, H, D) tensors; the kernels want head-major layouts.
On the CPU the kernels run in interpret mode (validation); the
production TPU path drops the same calls onto the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


@functools.partial(jax.jit, static_argnames=("scale", "causal"))
def flash_attention(q, k, v, *, scale=None, causal=True):
    """q: (B,S,H,D); k,v: (B,T,Hkv,D) with Hkv | H -> (B,S,H,D)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if Hkv != H:                       # GQA: expand kv heads to q heads
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _fa.flash_attention(qt, kt, vt, scale=scale, causal=causal)
    return out.transpose(0, 2, 1, 3)


def ssd_scan(cfg, x, Bm, Cm, dt, a, h0=None):
    """Model-layer adapter: x (B,S,H,P), Bm/Cm (B,S,G,N) group-mapped to
    heads, dt/a (B,S,H).  Returns (y (B,S,H,P) f32, h_final (B,H,P,N))."""
    B, S, H, P = x.shape
    G = Bm.shape[2]
    head_group = jnp.arange(H) // (H // G)
    Bh = Bm[:, :, head_group, :].transpose(0, 2, 1, 3)   # (B,H,S,N)
    Ch = Cm[:, :, head_group, :].transpose(0, 2, 1, 3)
    xt = x.transpose(0, 2, 1, 3)                          # (B,H,S,P)
    dtt = dt.transpose(0, 2, 1)
    at = a.transpose(0, 2, 1)
    y, h_final = _ssd.ssd_scan(xt, Bh, Ch, dtt, at, h0=h0, chunk=cfg.chunk)
    return y.transpose(0, 2, 1, 3), h_final


@jax.jit
def rmsnorm(x, scale):
    return _rn.rmsnorm(x, scale)
