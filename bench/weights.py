"""Weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights itself, so the plain reference can be given
the same arrays without taking anything the program made.  Every leaf of the
program's parameter tree is drawn from ``fold_in(key, leaf index)`` by a
rule chosen from its name:

  norm ``scale``       1 + 0.1 N(0, 1)    (so a skipped norm shows)
  bias ``b``           0
  ``A_log``            log U(1, 16)       (Mamba-2's published init)
  ``dt_bias``          softplus^-1 of dt, log dt ~ U(log 1e-3, log 1e-1)
  ``D``                1
  ``embedding``        N(0, 1/d)          (unit rms once scaled by sqrt(d))
  ``w``                N(0, 1/fan_in), fan_in = shape[-2] (K for a conv)

The tree's structure, shapes and dtypes come from ``jax.eval_shape`` of the
program's own init, so the program gets exactly the layout it expects.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key that depends on every bit of a seed wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def path_str(path) -> str:
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
    return "/".join(parts)


def _leaf(key, name: str, sds) -> jax.Array:
    parts = name.split("/")
    last = parts[-1]
    shape, f32 = sds.shape, jnp.float32
    if last == "scale":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    elif last == "b":
        x = jnp.zeros(shape, f32)
    elif last == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif last == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif last == "D":
        x = jnp.ones(shape, f32)
    elif last == "embedding":
        x = jax.random.normal(key, shape, f32) / math.sqrt(shape[-1])
    elif last == "w":           # (..., fan_in, out); a conv's is (..., K, C)
        x = jax.random.normal(key, shape, f32) / math.sqrt(shape[-2])
    else:
        raise ValueError(f"no init rule for parameter {name!r}")
    return x.astype(sds.dtype)


def builder(abstract):
    """-> build(key): parameters shaped like ``abstract`` (a tree of
    ShapeDtypeStruct), traceable, so a jitted function can remake them."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [path_str(p) for p, _ in flat]

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _leaf(jax.random.fold_in(key, i), n, s)
            for i, (n, (_, s)) in enumerate(zip(names, flat))])
    return build


def make_params(abstract, seed: int, shardings=None):
    """Parameters shaped like ``abstract``, drawn from ``seed``, placed by
    ``shardings`` (a matching tree, or None for the default device)."""
    kw = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(builder(abstract), **kw)(seed_key(seed))


def named_leaves(tree) -> dict:
    """{path string: leaf} for a parameter tree."""
    return {path_str(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
