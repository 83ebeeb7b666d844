"""Matrix products for the references: float32 at the highest precision, or
the control's float8 (e4m3, one scale per tensor, products and gradients
alike) that stands for "the nearest precision below bfloat16"."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def quant(x):
    """Round to float8 e4m3 under one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _swap(spec):
    ins, out = spec.split("->")
    a, b = ins.split(",")
    return a, b, out


def einsum(spec: str, a, b, lowp: bool = False):
    """``jnp.einsum(spec, a, b)`` in float32, or with both operands and, in
    the backward pass, the incoming gradient rounded to float8."""
    if not lowp:
        return _ein(spec, a, b)
    sa, sb, so = _swap(spec)

    @jax.custom_vjp
    def f(a, b):
        return _ein(spec, quant(a), quant(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        qa, qb, qg = quant(a), quant(b), quant(g)
        da = _ein(f"{so},{sb}->{sa}", qg, qb)
        db = _ein(f"{sa},{so}->{sb}", qa, qg)
        return da, db

    f.defvjp(fwd, bwd)
    return f(a.astype(jnp.float32), b.astype(jnp.float32))
