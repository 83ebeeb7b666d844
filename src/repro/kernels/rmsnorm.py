"""Fused RMSNorm — Pallas TPU kernel (row-tiled, one HBM round-trip)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

DEFAULT_BLOCK_ROWS = 256


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, scale, *, eps: float = 1e-6,
            block_rows: int = DEFAULT_BLOCK_ROWS,
            interpret: bool | None = None):
    """x: (..., D); scale: (D,)."""
    if interpret is None:
        interpret = default_interpret()
    orig_shape = x.shape
    D = x.shape[-1]
    x2 = x.reshape(-1, D)
    R = x2.shape[0]
    br = min(block_rows, R)
    pad = (-R) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(x2.shape[0] // br,),
        in_specs=[pl.BlockSpec((br, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
    )(x2, scale)
    return out[:R].reshape(orig_shape)
