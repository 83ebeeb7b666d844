"""The serving steps update the stacked caches in place.

``transformer._apply_segment`` carries a segment's stacked cache through its
layer scan and each layer writes only its new rows at its own index
(``layers.paged_write_gather``, ``layers.layer_index``).  These tests hold
the paged steps to the formulation this replaced, kept here as the
reference: each layer's cache a scanned input, written as a whole and
stacked again as the scan's output.  They also check that the compiled
paged decode holds no copy, slice or write-back of a whole pool.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import blocks as B
from repro.models import transformer as T
from repro.runtime import steps as ST
from serving_fixtures import (ARCH_BY_KEY, STRUCT_ARCH, paged_decode_compiled,
                              pool_moves)

SLOTS, BLOCK, N_BLOCKS = 4, 4, 24


def _reference_apply_segment(seg_params, blocks, arch, x, *, seg_cache=None,
                             x0=None, cross_input=None, shared=None,
                             positions=None, block_tables=None, new_lens=None,
                             slot_ids=None, impl="xla", unroll=1,
                             remat="none", act_sharding=None):
    """Each layer's cache is sliced out as a scan input, written whole, and
    stacked into the scan's output."""
    assert seg_cache is not None

    def body(carry, xs):
        x, aux = carry
        p_stack, c_stack = xs
        new = {}
        for i, kind in enumerate(blocks):
            bi = f"b{i}"
            x, new[bi], a = B.apply_block(
                p_stack[bi], kind, arch, x, x0=x0, cross_input=cross_input,
                shared=shared, cache=c_stack[bi], positions=positions,
                block_tables=block_tables, new_lens=new_lens,
                slot_ids=slot_ids, impl=impl)
            aux = aux + a
        return (x, aux), new

    (x, aux), ys = jax.lax.scan(body, (x, B.ZERO), (seg_params, seg_cache),
                                unroll=unroll)
    return x, aux, ys


def _randomized(tree, seed):
    """Random values in every floating leaf, the null block and the null
    slot row included, so that every row read or left alone shows."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return treedef.unflatten([
        jax.random.normal(k, t.shape).astype(t.dtype)
        if jnp.issubdtype(t.dtype, jnp.floating) else t
        for k, t in zip(keys, leaves)])


def _i32(*rows):
    return jnp.array(rows, jnp.int32)


# prefill: a chunk of 8 in three rows, two of them padded past new_lens;
# decode: four rows, the last idle (its table and slot id at the null block
# and the null slot row)
PAGED_INPUTS = {
    "prefill": dict(tokens=(3, 8), positions=_i32(0, 4, 0),
                    tables=_i32([1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 0, 0]),
                    new_lens=_i32(8, 3, 5), slot_ids=_i32(0, 1, 2)),
    "decode": dict(tokens=(4, 1), positions=_i32(8, 15, 6, 0),
                   tables=_i32([1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 0],
                               [0, 0, 0, 0]),
                   slot_ids=_i32(0, 1, 2, SLOTS)),
}


def _paged_run(arch, mode, pool_dtype):
    """-> (logits, pools) of one paged step on random weights and pools."""
    m = PAGED_INPUTS[mode]
    params = T.init_lm(jax.random.PRNGKey(0), arch)
    pools = _randomized(T.init_paged_cache(arch, N_BLOCKS, BLOCK, pool_dtype,
                                           slots=SLOTS), 1)
    tokens = jax.random.randint(jax.random.PRNGKey(2), m["tokens"], 0,
                                arch.vocab)
    if mode == "prefill":
        step = ST.make_paged_prefill_step(arch)
        args = (tokens, m["positions"], m["tables"], m["new_lens"],
                m["slot_ids"])
    else:
        step = ST.make_paged_decode_step(arch)
        args = (tokens, m["positions"], m["tables"], m["slot_ids"])
    return jax.jit(step)(params, pools, *args)


def _assert_same(got, want):
    (g_logits, g_cache), (w_logits, w_cache) = got, want
    assert jax.tree.structure(g_cache) == jax.tree.structure(w_cache)
    for g, w in zip(jax.tree.leaves(g_cache), jax.tree.leaves(w_cache)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for g, w in zip(jax.tree.leaves(g_logits), jax.tree.leaves(w_logits)):
        g, w = np.asarray(g), np.asarray(w)
        assert np.isfinite(g).all()
        np.testing.assert_array_equal(g.argmax(-1), w.argmax(-1))
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mode", sorted(PAGED_INPUTS))
@pytest.mark.parametrize("key", ["tiny", "mla"])
def test_paged_step_matches_per_layer_formulation(key, mode, pool_dtype,
                                                  monkeypatch):
    arch, dt = ARCH_BY_KEY[key], jnp.dtype(pool_dtype)
    got = _paged_run(arch, mode, dt)
    monkeypatch.setattr(T, "_apply_segment", _reference_apply_segment)
    _assert_same(got, _paged_run(arch, mode, dt))


@pytest.mark.parametrize("mode", sorted(PAGED_INPUTS))
@pytest.mark.parametrize("key", ["ssm", "hybrid", "cross", "shared",
                                 "encdec"])
def test_slot_state_paged_step_matches_per_layer_formulation(key, mode,
                                                             monkeypatch):
    arch = ARCH_BY_KEY[key]
    got = _paged_run(arch, mode, jnp.float32)
    monkeypatch.setattr(T, "_apply_segment", _reference_apply_segment)
    _assert_same(got, _paged_run(arch, mode, jnp.float32))


# --- structure ---------------------------------------------------------------

@pytest.mark.parametrize("formulation", ["in_place", "per_layer"])
def test_compiled_paged_decode_moves_no_whole_pool(formulation, monkeypatch):
    """On the CPU backend, float32 pools (it widens a bf16 scatter to
    float32 over the whole pool); tests/test_chip_compile.py compiles the
    bf16 decode for a v5e.  The per-layer formulation is the control: its
    slices, write-backs and copies of whole pools are what the detector
    has to find."""
    if formulation == "per_layer":
        monkeypatch.setattr(T, "_apply_segment", _reference_apply_segment)
    arch = STRUCT_ARCH
    compiled, cache_bytes = paged_decode_compiled(
        arch, num_blocks=512, block_size=BLOCK, slots=SLOTS, table_len=4)
    pool_shape = (512, BLOCK, arch.n_kv_heads, arch.resolved_head_dim)
    moves = pool_moves(compiled.as_text(), pool_shape, 3)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    # one stacked pool (K or V) is half the cache
    if formulation == "in_place":
        assert moves == []
        assert mem.temp_size_in_bytes < cache_bytes // 2
    else:
        assert moves
        assert mem.temp_size_in_bytes >= cache_bytes // 2
