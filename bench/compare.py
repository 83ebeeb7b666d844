"""The numbers that decide ``correct``, and how each is taken.

Training (the program against the reference, after the same steps):
  grad_gap    the worst leaf's |program norm - reference norm| of the first
              clipped gradient, over the larger of the reference's norm of
              that leaf and of the median leaf (compared)
  update_gap  as grad_gap, for each leaf's change over the first steps
              (compared)
  loss_gap    |program loss - reference loss| / |reference loss| of the
              first step (compared); its largest over the first steps is
              logged
A leaf is one parameter tensor of one layer.  Leaves whose reference
gradient is under a thousandth of the median leaf's are left out of both:
they move by round-off alone.

Serving:
  max_logit_gap  the widest gap, in nats, by which a served token's logit
                 lies below the reference's best at its position
"""
from __future__ import annotations

import numpy as np

EXCLUDE_BELOW = 1e-3


def leaf_norms(tree) -> dict:
    """{leaf name: norm} with each stacked layer of a leaf under
    ``segments/`` counted on its own (``name[i]``).  Runs jitted."""
    import jax
    import jax.numpy as jnp

    from bench.weights import named_leaves

    def norms(t):
        out = {}
        for name, x in named_leaves(t).items():
            x = x.astype(jnp.float32)
            if name.startswith("segments/"):
                out[name] = jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2, 1))
            else:
                out[name] = jnp.sqrt(jnp.sum(x * x))
        return out

    got = jax.device_get(jax.jit(norms)(tree))
    flat = {}
    for name, v in got.items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            flat.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            flat[name] = float(v)
    return flat


def norm_gap(prog: dict, ref: dict, ref_grad: dict) -> tuple[float, str]:
    """(worst gap, its leaf) over the leaves that count."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))[:5]}")
    med = float(np.median(list(ref.values())))
    gmed = float(np.median(list(ref_grad.values())))
    worst, where = 0.0, ""
    for k in ref:
        if ref_grad[k] < EXCLUDE_BELOW * gmed:
            continue
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not np.isfinite(g):
            return float("inf"), k
        if g > worst:
            worst, where = g, k
    return worst, where


def loss_gap(prog: list, ref: list) -> float:
    g = [abs(p - r) / abs(r) for p, r in zip(prog, ref)]
    return float(max(g)) if len(prog) == len(ref) and np.all(np.isfinite(g)) \
        else float("inf")
