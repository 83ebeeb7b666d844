"""The plain references agree with the program at smoke size on the CPU,
in float32, on weights drawn from the seed."""
from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import FIXTURES


def _cfg(name):
    return json.loads((FIXTURES / "configs" / f"{name}.json").read_text())


def _params(arch, seed):
    import jax

    from bench import weights as W
    from repro.models import transformer as T
    abstract = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), arch))
    return W.make_params(abstract, seed)


@pytest.mark.parametrize("seed", [0, 2**33 + 7])
def test_qwen3_logits_match_program(seed):
    import jax.numpy as jnp

    from bench.harness import arch_from_config
    from bench.reference import qwen3 as ref
    from bench.reference.lowp import einsum
    from bench.weights import named_leaves
    from repro.models import transformer as T

    cfg = _cfg("tiny-qwen3")
    arch = arch_from_config(cfg)
    assert arch.n_kv_heads < arch.n_heads            # grouped queries
    params = _params(arch, seed)
    toks = (np.arange(64) * 37 + seed) % cfg["vocab_size"]
    got = T.lm_apply(params, arch, jnp.asarray(toks)[None]).logits[0]
    w = ref.weights(named_leaves(params))
    h = ref.hidden(w, cfg, jnp.asarray(toks))
    want = einsum("sd,dv->sv", h, w["head"])
    V = cfg["vocab_size"]
    np.testing.assert_allclose(np.asarray(got[:, :V]),
                               np.asarray(want[:, :V]), atol=2e-4, rtol=2e-4)


def test_qwen3_gaps_of_own_argmax_are_zero():
    import jax.numpy as jnp

    from bench.harness import arch_from_config
    from bench.reference import qwen3 as ref
    from bench.reference.lowp import einsum
    from bench.weights import named_leaves

    cfg = _cfg("tiny-qwen3")
    params = _params(arch_from_config(cfg), 3)
    w = ref.weights(named_leaves(params))
    seq = list((np.arange(20) * 11) % cfg["vocab_size"])
    for _ in range(6):                 # greedy continuation by the reference
        h = ref.hidden(w, cfg, jnp.asarray(seq))
        seq.append(int(jnp.argmax(einsum("d,dv->v", h[-1],
                                         w["head"][:, :cfg["vocab_size"]]))))
    g = ref.gaps(w, cfg, np.asarray(seq, np.int32), 20, 32)
    assert g.shape == (6,) and np.max(g) < 1e-4


@pytest.mark.parametrize("seed", [0, 5])
def test_mamba2_loss_and_gradients_match_program(seed):
    import jax
    import jax.numpy as jnp

    from bench.harness import arch_from_config
    from bench.reference import mamba2 as ref
    from bench.traffic import train_batch
    from bench.weights import named_leaves
    from repro.runtime import steps as ST

    cfg = _cfg("tiny-mamba2")
    arch = arch_from_config(cfg)
    params = _params(arch, seed)
    b = train_batch({"seq_len": 48, "tokens": {"kind": "zipf", "a": 1.3}},
                    seed, 0, 2, cfg["vocab_size"])
    tok, lab = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
    loss_fn = ST.make_loss_fn(arch)
    got, g_prog = jax.value_and_grad(
        lambda p: loss_fn(p, tok, lab)[0])(params)
    w = ref.weights(named_leaves(params))
    want, g_ref = jax.value_and_grad(ref.loss)(w, cfg, tok, lab)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    g_prog, g_ref = named_leaves(g_prog), ref.by_path(g_ref)
    assert set(g_prog) == set(g_ref)
    for k in g_ref:
        scale = float(jnp.max(jnp.abs(g_ref[k]))) + 1e-12
        err = float(jnp.max(jnp.abs(g_prog[k] - g_ref[k])))
        assert err <= 2e-3 * scale, (k, err, scale)


def test_float8_einsum_rounds_and_differentiates():
    import jax
    import jax.numpy as jnp

    from bench.reference.lowp import einsum
    a = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    b = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
    hi, lo = einsum("ij,jk->ik", a, b), einsum("ij,jk->ik", a, b, lowp=True)
    rel = float(jnp.max(jnp.abs(hi - lo)) / jnp.max(jnp.abs(hi)))
    assert 1e-3 < rel < 0.2
    ga = jax.grad(lambda a: jnp.sum(einsum("ij,jk->ik", a, b, lowp=True)))(a)
    np.testing.assert_allclose(ga, jnp.broadcast_to(b.sum(1), (8, 16)),
                               rtol=0.15, atol=0.15)
