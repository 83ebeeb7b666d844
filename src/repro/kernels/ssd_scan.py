"""Mamba2 SSD chunked scan — Pallas TPU kernel.

Grid (B, H, num_chunks), chunk axis innermost/sequential; the recurrent
state h (P, N) lives in VMEM scratch and persists across the chunk axis.
Per chunk the intra-chunk quadratic term (Q x Q decay-weighted scores) runs
on the MXU; the inter-chunk term applies the carried state.  Q = chunk = 128
keeps the score matmul MXU-shaped.

Inputs are pre-mapped per head (groups broadcast to heads by ops.py):
    x  (B, H, S, P)   dt,a (B, H, S)   Bm,Cm (B, H, S, N)
Outputs: y (B, H, S, P) f32, h_final (B, H, P, N) f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

DEFAULT_CHUNK = 128


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, h0_ref,
                y_ref, hout_ref, h_ref, *, chunk: int):
    c_idx = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    Bm = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)         # (Q, N)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (1, Q) row
    a = a_ref[0, 0].astype(jnp.float32)          # (1, Q) row

    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iota_i >= iota_j
    eye = iota_i == iota_j

    # Rows and columns swap by a masked sum over the diagonal, and the
    # prefix sum is a masked sum under the causal mask: exact f32 on the
    # vector unit, with no transpose or cumsum for Mosaic to lower.
    def col(row):                                # (1, Q) -> (Q, 1)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def row(col):                                # (Q, 1) -> (1, Q)
        return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)

    cum_c = jnp.sum(jnp.where(causal, a, 0.0), axis=1,
                    keepdims=True)               # (Q, 1): sum_{k<=i} a_k
    cum_r = row(cum_c)                           # (1, Q)
    cum_last = jnp.sum(a, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk: scores[i,j] = (C_i . B_j) dt_j exp(cum_i - cum_j), j <= i
    decay = jnp.where(causal, jnp.exp(cum_c - cum_r), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    scores = cb * decay * dt
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,P)

    # inter-chunk: y_i += exp(cum_i) * C_i . h_prev
    h = h_ref[...]                               # (P, N)
    y = y + jnp.exp(cum_c) * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # (Q,N)x(P,N)^T -> (Q,P)

    # state update: h' = exp(cum_Q) h + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T
    w = jnp.exp(cum_last - cum_c) * col(dt) * x                   # (Q,P)
    h_new = jnp.exp(cum_last) * h + jax.lax.dot_general(
        w, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (P,N)
    h_ref[...] = h_new
    y_ref[0, 0] = y

    @pl.when(c_idx == nc - 1)
    def _final():
        hout_ref[0, 0] = h_new


def ssd_scan(x, Bm, Cm, dt, a, h0=None, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool | None = None):
    """See module docstring for shapes."""
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    if interpret is None:
        interpret = default_interpret()
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:  # a=0, dt=0 padding leaves the state untouched
        def padf(t):
            return jnp.pad(t, [(0, 0), (0, 0), (0, pad)]
                           + [(0, 0)] * (t.ndim - 3))
        x, Bm, Cm, dt, a = map(padf, (x, Bm, Cm, dt, a))
    nc = x.shape[2] // Q
    # dt and a go in as (B, H, 1, S): a (1, Q) block is a lane-dense row,
    # which Mosaic tiles; a (1, 1, Q) block of (B, H, S) it refuses
    dt, a = dt[:, :, None, :], a[:, :, None, :]
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)

    kernel = functools.partial(_ssd_kernel, chunk=Q)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc * Q, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, Bm, Cm, dt, a, h0)
    return y[:, :, :S], h_final
