"""Per-assigned-architecture smoke tests (deliverable f): reduced same-family
config, one forward + one train step on CPU, asserting shapes + no NaNs.
The FULL configs are exercised only via the dry-run (no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduce_for_smoke
from repro.models import transformer as T
from repro.optim import optimizers as O
from repro.runtime import steps as ST


def _frontend(arch, B):
    if arch.frontend == "vision":
        return jnp.ones((B, arch.n_img_tokens, arch.d_model), jnp.float32)
    if arch.frontend == "audio":
        return jnp.ones((B, arch.encoder.seq_len, arch.d_model), jnp.float32)
    return None


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_forward_smoke(name):
    arch = reduce_for_smoke(ARCHS[name])
    params = T.init_lm(jax.random.PRNGKey(0), arch)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, arch.vocab)
    out = T.lm_apply(params, arch, toks, frontend=_frontend(arch, B))
    assert out.logits.shape == (B, S, arch.padded_vocab)
    assert not np.any(np.isnan(np.asarray(out.logits))), name


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_train_step_smoke(name):
    arch = reduce_for_smoke(ARCHS[name])
    opt = O.adamw(1e-3)
    step = ST.make_train_step(arch, opt)
    params = T.init_lm(jax.random.PRNGKey(0), arch)
    ostate = opt[0](params)
    B, S = 2, 16
    key = jax.random.PRNGKey(2)
    batch = {"tokens": jax.random.randint(key, (B, S), 0, arch.vocab),
             "labels": jax.random.randint(key, (B, S), 0, arch.vocab)}
    fe = _frontend(arch, B)
    if fe is not None:
        batch["frontend"] = fe
    params2, ostate2, metrics = jax.jit(step)(params, ostate, batch)
    assert np.isfinite(float(metrics["loss"])), name
    assert np.isfinite(float(metrics["grad_norm"])), name
    # parameters changed
    delta = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), params, params2)))
    assert delta > 0, name


@pytest.mark.parametrize("name", ["qwen3-8b", "mamba2-780m", "zamba2-2.7b"])
def test_decode_step_smoke(name):
    arch = reduce_for_smoke(ARCHS[name])
    params = T.init_lm(jax.random.PRNGKey(0), arch)
    B, S, BS, MB = 2, 8, 4, 6          # 6 blocks of 4: 24 tokens a row
    cache = T.init_paged_cache(arch, B * MB + 1, BS, jnp.float32, slots=B)
    tables = 1 + jnp.arange(B * MB, dtype=jnp.int32).reshape(B, MB)
    slot_ids = jnp.arange(B, dtype=jnp.int32)
    pre = ST.make_paged_prefill_step(arch)
    dec = ST.make_paged_decode_step(arch)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, arch.vocab)
    logits, cache = jax.jit(pre)(params, cache, toks,
                                 jnp.zeros((B,), jnp.int32), tables,
                                 jnp.full((B,), S, jnp.int32), slot_ids)
    assert logits.shape == (B, arch.padded_vocab)
    logits2, cache = jax.jit(dec)(params, cache, toks[:, :1],
                                  jnp.full((B,), S, jnp.int32), tables,
                                  slot_ids)
    assert not np.any(np.isnan(np.asarray(logits2)))
