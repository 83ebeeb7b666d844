"""The one traffic generator.  A mix is a data file; this reads it.

Serving mixes (open loop): ``rate_per_s`` x ``seconds`` requests are due in
the window.  Their lengths and the gaps between their due times are drawn
once from the mix's own ``shape_seed``, so every run seed gets the same set
of sizes and arrivals; the run seed draws the token ids and, where the mix's
``order`` is ``"seed"``, shuffles the sizes and gaps.  With ``order``
``"fixed"`` they come in the order drawn, the same for every seed: under
bursty arrivals the order of long prompts is itself the work.  The gaps are
scaled to fill the window exactly.

  prompt_len / output_len   {"kind": "lognormal", "median", "sigma",
                             "min", "max"}  (clipped)
  arrival                   {"kind": "gamma", "cv"}  (cv 1 is Poisson)

Training mixes: each step's rows are ``batch_per_chip`` x chips sequences
of ``seq_len`` + 1 tokens drawn from ``tokens`` ({"kind": "zipf", "a"}),
from (seed, step), so every row differs.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    index: int
    due_s: float            # offset from the window's start
    prompt: np.ndarray      # (prompt_len,) int32
    max_new_tokens: int


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["kind"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['kind']!r}")
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(spec: dict, n: int, rng) -> np.ndarray:
    if spec["kind"] != "gamma":
        raise ValueError(f"unknown arrival process {spec['kind']!r}")
    shape = 1.0 / spec["cv"] ** 2          # gamma with mean 1 and this cv
    return rng.gamma(shape, 1.0 / shape, n)


def requests(mix: dict, seed: int, seconds: float, vocab: int,
             rate_per_s: float | None = None) -> list[Arrival]:
    """The requests due in a window of ``seconds``, in due order."""
    rate = mix["rate_per_s"] if rate_per_s is None else rate_per_s
    n = max(int(round(rate * seconds)), 1)
    shape_rng = np.random.default_rng(mix["shape_seed"])
    p_len = _lengths(mix["prompt_len"], n, shape_rng)
    o_len = _lengths(mix["output_len"], n, shape_rng)
    gaps = _gaps(mix["arrival"], n, shape_rng)
    rng = np.random.default_rng(seed)
    order = mix.get("order", "seed")
    if order == "seed":
        p_len, o_len, gaps = (x[rng.permutation(n)]
                              for x in (p_len, o_len, gaps))
    elif order != "fixed":
        raise ValueError(f"unknown order {order!r}")
    # first due at 0, last due just before the window closes
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due *= seconds * (1 - 0.5 / n) / max(due[-1], 1e-9) if n > 1 else 0.0
    return [Arrival(i, float(due[i]),
                    rng.integers(1, vocab, int(p_len[i])).astype(np.int32),
                    int(o_len[i]))
            for i in range(n)]


def train_batch(mix: dict, seed: int, step: int, rows: int,
                vocab: int) -> dict:
    """One step's rows: {"tokens", "labels"}, each (rows, seq_len) int32."""
    spec = mix["tokens"]
    if spec["kind"] != "zipf":
        raise ValueError(f"unknown token distribution {spec['kind']!r}")
    rng = np.random.default_rng((seed, step))
    z = rng.zipf(spec["a"], size=(rows, mix["seq_len"] + 1))
    toks = np.minimum(z - 1, vocab - 1).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
