"""Continuous-batching serving subsystem tests: paged-cache invariants,
scheduler admission/preemption policy, the v2 generation API
(SamplingParams validation, seeded stochastic decode, stop conditions,
typed RequestOutput, generate/stream/on_token), and greedy-decode parity
for every architecture family the engine serves — attention-only,
pure-SSM, hybrid, cross-attention, zamba2's weight-shared block, whisper's
encoder-decoder and MLA latent attention.

Greedy parity is asserted against tests/goldens_serving.json — token
sequences frozen from the retired wave Server (see gen_serving_goldens.py).
The pinned goldens keep parity falsifiable: the engine is the only serving
path left, so a live comparison would be circular.  Stochastic decode has no goldens: its contract
is determinism — bit-identical reruns, seed sensitivity, and invariance
under forced recompute-preemption — which the sampling tests pin instead.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import ARCHS
from repro.configs.base import ArchConfig, Segment, ShapeSpec
from repro.core.asa import AdaptiveScheduler
from repro.launch.mesh import make_host_mesh, mesh_shape_of
from repro.models import layers as L
from repro.models import transformer as T
from repro.runtime import steps as ST
from repro.serving import (BlockAllocator, ContinuousBatchingEngine,
                           PagedKVCache, Request, RequestOutput,
                           RequestScheduler, SamplingParams, ServingMetrics,
                           UnifiedCacheManager)
from repro.serving.cache_manager import check_servable
from repro.serving.engine import _ReqState
from repro.serving.paged_cache import NULL_BLOCK, PagedCacheConfig, blocks_for
from repro.serving.sampling import apply_top_k, apply_top_p
from serving_fixtures import (ARCH_BY_KEY, TINY, TINY_CROSS, TINY_ENCDEC,
                              TINY_HYBRID, TINY_MLA, TINY_SHARED, TINY_SSM,
                              load_goldens, scenario_requests)

_PARAMS_CACHE: dict[str, dict] = {}


def _params_for(arch):
    if arch.name not in _PARAMS_CACHE:
        # goldens_serving.json was recorded with the non-partitionable
        # threefry PRNG; jax now defaults to the partitionable one, which
        # draws other weights from the same key.  Pin the mode the goldens
        # were made under so the weights (and hence the tokens) match.
        with jax.threefry_partitionable(False):
            _PARAMS_CACHE[arch.name] = T.init_lm(jax.random.PRNGKey(0),
                                                 arch)
    return _PARAMS_CACHE[arch.name]


def _run_scenario(name, mesh, sampling=None, **engine_kw):
    arch, reqs, slots, max_len = scenario_requests(name)
    eng = ContinuousBatchingEngine(arch, _params_for(arch), mesh,
                                   slots=slots, max_len=max_len, **engine_kw)
    outs = eng.generate([
        Request(id=rid, prompt=prompt.copy(), max_new_tokens=max_new,
                sampling=sampling or SamplingParams())
        for rid, prompt, max_new in reqs])
    return eng, {o.request_id: o.token_ids for o in outs}


# ---------------------------------------------------------------------------
# paged cache
# ---------------------------------------------------------------------------

def test_block_allocator_invariants():
    a = BlockAllocator(8)                     # blocks 1..7 usable
    assert a.num_free == 7
    got = a.alloc(3)
    assert len(got) == 3 and NULL_BLOCK not in got
    assert a.num_free == 4 and a.num_used == 3
    # all-or-nothing: over-ask leaves state untouched
    assert a.alloc(5) is None
    assert a.num_free == 4
    a.free(got[:2])
    assert a.num_free == 6
    with pytest.raises(ValueError):           # double free
        a.free(got[:1])
    with pytest.raises(ValueError):           # null block is never freeable
        a.free([NULL_BLOCK])
    # freed blocks are reused
    again = a.alloc(6)
    assert again is not None and set(got[:2]) <= set(again)


def test_block_allocator_refcount_invariants():
    """Shared blocks: freed only at refcount 0; incref on unallocated /
    null blocks raises; double free still raises after the last ref."""
    a = BlockAllocator(8)
    (b,) = a.alloc(1)
    assert a.refcount(b) == 1
    assert a.incref(b) == 2
    a.free([b])                               # one holder drops out
    assert a.refcount(b) == 1 and a.num_free == 6   # NOT freed yet
    assert a.decref(b) == 0                   # last holder -> free list
    assert a.num_free == 7 and a.refcount(b) == 0
    with pytest.raises(ValueError):           # double free
        a.decref(b)
    with pytest.raises(ValueError):           # incref on a free block
        a.incref(b)
    with pytest.raises(ValueError):           # null block is never refable
        a.incref(NULL_BLOCK)
    with pytest.raises(ValueError):
        a.decref(NULL_BLOCK)


def test_paged_cache_reserve_release_reuse():
    cache = PagedKVCache(TINY, PagedCacheConfig(block_size=4, num_blocks=9,
                                                max_blocks_per_seq=4),
                         dtype=np.float32)
    assert cache.reserve(0, 10)               # 3 blocks
    assert cache.allocator.num_used == 3
    assert cache.reserve(0, 12)               # same 3 blocks suffice
    assert cache.allocator.num_used == 3
    assert cache.reserve(0, 13)               # grows by one
    assert cache.allocator.num_used == 4
    assert cache.reserve(1, 16)               # 4 more -> pool full (8 usable)
    assert not cache.reserve(2, 1)            # OOM, state unchanged
    assert 2 not in cache.tables
    cache.release(0)
    assert cache.allocator.num_used == 4
    assert cache.reserve(2, 16)               # reuses request 0's blocks
    row = cache.table_row(2)
    assert row.shape == (4,) and NULL_BLOCK not in row
    assert (cache.table_row(None) == NULL_BLOCK).all()


def test_blocks_for():
    assert blocks_for(1, 4) == 1
    assert blocks_for(4, 4) == 1
    assert blocks_for(5, 4) == 2


# ---------------------------------------------------------------------------
# shared-prefix block reuse (host-side cache semantics)
# ---------------------------------------------------------------------------

def _prefix_cache(num_blocks=9, block_size=4, mbps=6):
    return PagedKVCache(TINY, PagedCacheConfig(block_size, num_blocks, mbps,
                                               share_prefix=True),
                        dtype=np.float32)


def test_prefix_match_assign_and_refcounts():
    cache = _prefix_cache()
    toks = np.arange(1, 13, dtype=np.int32)        # 3 full blocks
    assert cache.reserve(0, 12)
    cache.commit_prefix(0, toks, 12)               # request 0 wrote them
    t0 = list(cache.tables[0])
    assert all(cache.allocator.refcount(b) == 2 for b in t0)  # req + index
    # a second request with the same prefix + a private tail shares them
    toks2 = np.concatenate([toks, np.asarray([99, 98], np.int32)])
    assert cache.match_prefix(toks2) == t0
    n = cache.assign_prefix(1, toks2)
    assert n == 12 and cache.tables[1] == t0
    assert all(cache.allocator.refcount(b) == 3 for b in t0)
    assert cache.reserve(1, len(toks2))            # grows by one private block
    assert cache.tables[1][:3] == t0 and len(cache.tables[1]) == 4
    # releases peel references one at a time; blocks free only at zero
    cache.release(0)
    assert all(cache.allocator.refcount(b) == 2 for b in t0)
    assert cache.num_cached == 0                   # still referenced by req 1
    cache.release(1)
    assert all(cache.allocator.refcount(b) == 1 for b in t0)  # index holds
    assert cache.num_cached == 3                   # retired into the LRU
    # an identical context re-matches the retired blocks out of the LRU
    assert cache.assign_prefix(2, toks2) == 12
    assert cache.num_cached == 0
    assert cache.prefix_stats()["hit_rate"] > 0


def test_prefix_match_requires_full_blocks_and_leaves_one_token():
    cache = _prefix_cache()
    toks = np.arange(1, 11, dtype=np.int32)        # 2 full blocks + 2 spare
    cache.reserve(0, 10)
    cache.commit_prefix(0, toks, 10)               # only 2 full blocks indexed
    assert len(cache.match_prefix(toks)) == 2
    # a context that IS exactly the cached blocks must leave >= 1 token to
    # prefill (the engine needs logits to sample the first output token)
    assert len(cache.match_prefix(toks[:8])) == 1
    # partial-block prefix: no match below one full block
    assert cache.match_prefix(toks[:3]) == []
    # different first block: chain breaks immediately
    other = toks.copy(); other[0] = 77
    assert cache.match_prefix(other) == []


def test_prefix_lru_eviction_before_oom_never_evicts_referenced():
    cache = _prefix_cache(num_blocks=7)            # 6 usable
    a = np.arange(1, 9, dtype=np.int32)            # 2 blocks
    b = np.arange(101, 109, dtype=np.int32)        # 2 blocks
    cache.reserve(0, 8);  cache.commit_prefix(0, a, 8)
    cache.reserve(1, 8);  cache.commit_prefix(1, b, 8)
    cache.release(0)                               # a's blocks -> LRU
    live = list(cache.tables[1])
    # request 2 needs 4 blocks: 2 free + 2 evicted from the LRU (a's),
    # while request 1's referenced blocks are untouched
    c = np.arange(201, 217, dtype=np.int32)
    assert cache.can_fit(16)
    assert cache.reserve(2, 16)
    assert cache.tables[1] == live
    assert cache.prefix_stats()["evictions"] == 2
    assert cache.match_prefix(np.concatenate([a, [9]])) == []   # a evicted
    assert len(cache.match_prefix(np.concatenate([b, [9]]))) == 2  # b cached
    # pool genuinely exhausted now: no free, no LRU, reserve reports OOM
    assert not cache.reserve(3, 4)
    assert 3 not in cache.tables


def test_prefix_partial_eviction_sacrifices_chain_tail_first():
    """Regression: release() retired a chain head-first into the LRU, so a
    partial eviction removed the head block — match_prefix then broke at
    block 0 while the still-cached tail sat unmatchable.  Eviction must eat
    a retired chain from its tail."""
    cache = _prefix_cache(num_blocks=5)            # 4 usable
    toks = np.arange(1, 13, dtype=np.int32)        # 3 full blocks
    cache.reserve(0, 12)
    cache.commit_prefix(0, toks, 12)
    cache.release(0)                               # whole chain -> LRU
    assert cache.num_cached == 3
    cache.reserve(1, 8)                            # needs 2: 1 free + 1 evict
    assert cache.prefix_stats()["evictions"] == 1
    # the surviving 2 cached blocks are the chain HEAD — still matchable
    assert len(cache.match_prefix(toks)) == 2


def test_prefix_commit_dedups_duplicate_content():
    """Two requests that prefilled the same tokens privately (admitted
    before either committed): first writer wins the index entry, the
    second stays private and frees outright on release."""
    cache = _prefix_cache()
    toks = np.arange(1, 9, dtype=np.int32)
    cache.reserve(0, 8)
    cache.reserve(1, 8)
    cache.commit_prefix(0, toks, 8)
    cache.commit_prefix(1, toks, 8)                # duplicate content
    t0, t1 = cache.tables[0], cache.tables[1]
    assert all(cache.allocator.refcount(x) == 2 for x in t0)
    assert all(cache.allocator.refcount(x) == 1 for x in t1)
    free_before = cache.allocator.num_free
    cache.release(1)                               # private -> freed
    assert cache.allocator.num_free == free_before + 2
    cache.release(0)                               # indexed -> LRU
    assert cache.num_cached == 2


def test_prefix_sharing_rejected_for_slot_state_archs():
    """Slot-state rows (mamba2 recurrent state, cross-attn / wdec K/V) are
    per-request and cannot be content-shared — a precise error, not silent
    corruption."""
    mesh = make_host_mesh()
    for arch in (TINY_SSM, TINY_HYBRID, TINY_CROSS, TINY_SHARED, TINY_ENCDEC):
        with pytest.raises(ValueError, match="slot-state"):
            ContinuousBatchingEngine(arch, _params_for(arch), mesh, slots=2,
                                     max_len=64, share_prefix=True)


def test_paged_cache_specs_match_pool_tree():
    mesh = make_host_mesh()
    for arch in (TINY, TINY_HYBRID, TINY_CROSS, TINY_SSM, TINY_SHARED,
                 TINY_ENCDEC, TINY_MLA):
        plan = AdaptiveScheduler(faithful=False).plan(
            arch, ShapeSpec("serve", 64, 2, "decode"), mesh_shape_of(mesh))
        pools = T.init_paged_cache(arch, 8, 4, np.float32, slots=2)
        specs = plan.paged_cache_specs()
        assert jax.tree.structure(pools) == jax.tree.structure(specs), \
            arch.name


def test_check_servable_accepts_every_registry_arch():
    """The continuous engine serves every config in the zoo — zamba2's
    weight-shared block, whisper's encoder-decoder and deepseek's MLA
    included; check_servable only rejects kinds the serving cache layer has
    never seen."""
    for arch in ARCHS.values():
        check_servable(arch)                  # must not raise
    for arch in ARCH_BY_KEY.values():
        check_servable(arch)
    bogus = ArchConfig(name="tiny-unknown", family="dense", n_layers=1,
                       d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                       vocab=256, pattern=(Segment(("enc_attn",), 1),),
                       dtype="float32", param_dtype="float32")
    with pytest.raises(ValueError, match="enc_attn"):
        check_servable(bogus)
    # an encoder arch whose pattern has no wdec block would silently serve
    # raw (un-encoded) frontend projections — must be rejected up front
    import dataclasses
    no_wdec = dataclasses.replace(TINY_CROSS, name="tiny-enc-no-wdec",
                                  encoder=TINY_ENCDEC.encoder)
    with pytest.raises(ValueError, match="wdec"):
        check_servable(no_wdec)


def test_unified_cache_manager_slot_rows():
    """Slot-state pools carry one row per engine slot plus the reserved
    null row; inactive batch rows map to the null row."""
    cfg = PagedCacheConfig(block_size=4, num_blocks=9, max_blocks_per_seq=4,
                           slots=3)
    mgr = UnifiedCacheManager(TINY_HYBRID, cfg, dtype=np.float32)
    assert mgr.has_slot_state and mgr.slot_state_kinds == ["mamba2"]
    assert mgr.null_slot == 3
    ssm_pool = mgr.pools[0]["b1"]["ssm"]
    assert ssm_pool.shape[1] == 4                  # slots + null row
    # rows are _Slot.idx values (None -> null row), NOT list positions —
    # the engine's slot list may be reordered relative to pool rows
    assert (mgr.slot_ids_array([2, None, 0])
            == np.array([2, 3, 0], np.int32)).all()
    # block side inherited unchanged
    assert mgr.reserve(0, 10) and mgr.allocator.num_used == 3
    mgr.release(0)
    assert mgr.allocator.num_used == 0
    with pytest.raises(ValueError, match="slots"):
        UnifiedCacheManager(TINY_HYBRID,
                            PagedCacheConfig(4, 9, 4), dtype=np.float32)


def test_wdec_pool_carries_both_state_classes():
    """whisper's wdec block pages its self-attn KV and slot-indexes its
    per-request encoder cross K/V."""
    mgr = UnifiedCacheManager(
        TINY_ENCDEC, PagedCacheConfig(block_size=4, num_blocks=9,
                                      max_blocks_per_seq=4, slots=2),
        dtype=np.float32)
    assert mgr.slot_state_kinds == ["wdec"]
    pool = mgr.pools[0]["b0"]
    assert pool["self"]["k"].shape[1] == 9         # (repeat, NB, BS, H, D)
    assert pool["cross"]["k"].shape[1:3] == (3, 8)  # (slots+1, enc_len)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def _req(i, plen=8, max_new=4, priority=0):
    """Scheduler-protocol record: the scheduler queues the engine's
    internal _ReqState (the public Request is input-only and carries no
    out_tokens / bookkeeping fields)."""
    r = Request(id=i, prompt=np.arange(1, plen + 1, dtype=np.int32),
                max_new_tokens=max_new, priority=priority)
    return _ReqState(req=r, seed=i, stop_ids=frozenset())


def test_scheduler_fcfs_within_priority_class():
    s = RequestScheduler()
    for i in range(3):
        s.submit(_req(i))
    urgent = _req(99, priority=-1)
    s.submit(urgent)
    order = [s.next_admission().id for _ in range(4)]
    assert order == [99, 0, 1, 2]


def test_scheduler_token_budget_blocks_admission():
    s = RequestScheduler(max_tokens_in_flight=30)
    s.submit(_req(0, plen=8, max_new=4))      # footprint 12
    s.submit(_req(1, plen=8, max_new=4))
    s.submit(_req(2, plen=8, max_new=4))
    a, b = s.next_admission(), s.next_admission()
    assert a.id == 0 and b.id == 1
    assert s.next_admission() is None         # 24 + 12 > 30
    s.on_finish(a)
    assert s.next_admission().id == 2
    with pytest.raises(ValueError):           # can never be admitted
        s.submit(_req(3, plen=40, max_new=4))


def test_scheduler_footprint_capped_at_max_len():
    """Regression: the scheduler charged len(prompt) + max_new_tokens
    uncapped while the engine truncates every request to max_len, so a
    long-prompt request over-charged the budget and stalled admission.
    With the cap threaded through, a budget sized for capped footprints
    admits them."""
    s = RequestScheduler(max_tokens_in_flight=40, footprint_cap=32)
    # uncapped footprint 20 + 30 = 50 > 40 -> would have been rejected at
    # submit; capped at 32 it fits the budget
    s.submit(_req(0, plen=20, max_new=30))
    assert s._footprint(_req(0, plen=20, max_new=30)) == 32
    assert s.next_admission().id == 0
    # a second capped request must NOT be admitted (32 + 32 > 40) ...
    s.submit(_req(1, plen=20, max_new=30))
    assert s.next_admission() is None
    # ... and accounting symmetry: finish releases exactly the capped charge
    s.on_finish(_req(0, plen=20, max_new=30))
    assert s.next_admission().id == 1

    # the engine threads its max_len into a default scheduler
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(
        TINY, _params_for(TINY), mesh, slots=2, max_len=32, block_size=4,
        prefill_chunk=8, scheduler=RequestScheduler(max_tokens_in_flight=40))
    assert eng.scheduler.footprint_cap == 32
    eng.submit(Request(id=0, prompt=np.arange(1, 21, dtype=np.int32),
                       max_new_tokens=30))
    eng.run_until_drained()
    assert len(eng.completed) == 1
    assert len(eng.completed[0].token_ids) == 12    # truncated at max_len
    assert eng.completed[0].finish_reason == "length"
    # the engine OWNS the cap: a scheduler reused with a second engine must
    # pick up that engine's max_len, not keep the first one's stale cap
    eng2 = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                    max_len=16, block_size=4,
                                    prefill_chunk=8, scheduler=eng.scheduler)
    assert eng2.scheduler.footprint_cap == 16


def test_scheduler_releases_exactly_the_charged_footprint():
    """Regression: a cap change while a request is in flight must not leak
    budget — on_finish releases the footprint charged at admission, not a
    re-computed one under the new cap."""
    s = RequestScheduler(max_tokens_in_flight=40, footprint_cap=32)
    r = _req(0, plen=20, max_new=30)          # charged min(50, 32) = 32
    s.submit(r)
    assert s.next_admission() is r
    s.footprint_cap = 16                      # e.g. reused with a new engine
    s.on_finish(r)                            # releases the recorded 32
    assert s._in_flight_tokens == 0


def test_scheduler_preemption_victim_and_requeue_order():
    s = RequestScheduler()
    for i in range(3):
        s.submit(_req(i))
    running = [s.next_admission() for _ in range(2)]
    running[0].out_tokens = [1, 2, 3]         # largest resident footprint
    running[1].out_tokens = [1]
    victim = s.pick_preemption_victim(running)
    assert victim.id == 0
    s.preempt(victim)
    # preempted request keeps its original arrival seq: head of its class
    assert s.next_admission().id == 0
    # priority dominates footprint
    hi = _req(7, priority=-1); hi.out_tokens = [1, 2, 3, 4]
    assert s.pick_preemption_victim([hi, running[1]]).id == running[1].id


def test_preemption_does_not_inflate_lifecycle_counters():
    """Regression (found while cross-validating sanitizer counters against
    scheduler telemetry): preempt() used to route through on_finish() +
    submit(), so every preemption bumped both `released` and `submitted`
    — the exported Prometheus/JSONL lifecycle counters overstated client
    submissions and completions whenever the engine ran under cache
    pressure.  A preemption is neither: only `preemptions` may move."""
    s = RequestScheduler(max_tokens_in_flight=100)
    r = _req(0)
    s.submit(r)
    assert s.next_admission() is r
    seq = r._sched_seq
    s.preempt(r)
    assert s.stats == {"submitted": 1, "admitted": 1, "budget_refusals": 0,
                       "preemptions": 1, "released": 0}
    assert s._in_flight_tokens == 0           # budget charge still released
    assert r._sched_seq == seq                # head-of-class re-entry kept
    assert s.next_admission() is r
    s.on_finish(r)
    assert s.stats["released"] == 1 and s.stats["submitted"] == 1

    # end-to-end: under forced preemption, submitted == client submissions
    # and released == completions
    eng = ContinuousBatchingEngine(
        TINY, _params_for(TINY), make_host_mesh(), slots=3, max_len=64,
        num_blocks=10, block_size=4, prefill_chunk=8)
    reqs = [Request(id=i, prompt=np.arange(1, 11, dtype=np.int32),
                    max_new_tokens=8) for i in range(6)]
    eng.generate(reqs)
    st = eng.scheduler.stats
    assert st["preemptions"] > 0              # pressure actually happened
    assert st["submitted"] == len(reqs)
    assert st["released"] == len(reqs)
    assert st["admitted"] == len(reqs) + st["preemptions"]  # re-admissions


def test_preemption_victim_ranks_by_resident_footprint():
    """Regression: the docstring promises 'frees the most blocks per
    preemption' but the ranking used len(out_tokens) — a long-prompt
    request mid-prefill (0 generated tokens, many resident blocks) was
    ranked LAST.  Rank by len(context()) = tokens in cache instead."""
    s = RequestScheduler()
    big = _req(0, plen=40, max_new=4)          # mid-prefill: 40 resident
    small = _req(1, plen=4, max_new=16)
    s.submit(big); s.submit(small)
    s.next_admission(); s.next_admission()
    small.out_tokens = list(range(10))         # long-running, 14 resident
    assert s.pick_preemption_victim([small, big]) is big
    # generated tokens still count toward footprint: 4+20 > 8+10
    small2 = _req(2, plen=8, max_new=16); small2.out_tokens = list(range(10))
    grown = _req(3, plen=4, max_new=24); grown.out_tokens = list(range(20))
    assert s.pick_preemption_victim([small2, grown]) is grown


# ---------------------------------------------------------------------------
# engine: greedy parity against the wave-era goldens
# ---------------------------------------------------------------------------

# every arch family, with chunked prefill (chunk < prompt) and slot churn
PARITY_CASES = [
    ("tiny/base",   dict(block_size=4, prefill_chunk=3)),
    ("ssm/base",    dict(block_size=4, prefill_chunk=3)),
    ("hybrid/base", dict(block_size=4, prefill_chunk=4)),
    ("cross/base",  dict(block_size=4, prefill_chunk=4)),
    ("shared/base", dict(block_size=4, prefill_chunk=3)),
    ("encdec/base", dict(block_size=4, prefill_chunk=3)),
    ("mla/base",    dict(block_size=4, prefill_chunk=3)),
]


@pytest.mark.parametrize("scenario,kw", PARITY_CASES,
                         ids=[c[0] for c in PARITY_CASES])
def test_greedy_parity_with_wave_goldens(scenario, kw):
    mesh = make_host_mesh()
    eng, got = _run_scenario(scenario, mesh, **kw)
    assert got == load_goldens(scenario), scenario
    assert eng.cache.allocator.num_used == 0  # every block returned
    assert eng.metrics.summary()["completed"] == len(got)


# tiny block pools force recompute-preemption mid-decode; the resume must
# rebuild paged KV, SSM slot state, latent pools and cross K/V exactly
PREEMPT_CASES = [
    ("tiny/preempt",   dict(block_size=4, num_blocks=8, prefill_chunk=8)),
    ("hybrid/preempt", dict(block_size=4, num_blocks=8, prefill_chunk=8)),
    ("shared/preempt", dict(block_size=4, num_blocks=8, prefill_chunk=8)),
    ("encdec/preempt", dict(block_size=4, num_blocks=8, prefill_chunk=8)),
    ("mla/preempt",    dict(block_size=4, num_blocks=8, prefill_chunk=8)),
]


@pytest.mark.parametrize("scenario,kw", PREEMPT_CASES,
                         ids=[c[0] for c in PREEMPT_CASES])
def test_parity_under_forced_preemption(scenario, kw):
    mesh = make_host_mesh()
    eng, got = _run_scenario(scenario, mesh, **kw)
    assert got == load_goldens(scenario), scenario
    assert eng.metrics.preemptions > 0
    assert eng.cache.allocator.num_used == 0


# prefix sharing must be invisible to greedy outputs: the two purely paged
# families run the pinned-golden scenarios again with sharing ON, including
# forced preemption of a sharing request (its retired blocks re-match at
# re-admission)
SHARING_PARITY_CASES = [
    ("tiny/base",    dict(block_size=4, prefill_chunk=3)),
    ("mla/base",     dict(block_size=4, prefill_chunk=3)),
    ("tiny/preempt", dict(block_size=4, num_blocks=8, prefill_chunk=8)),
    ("mla/preempt",  dict(block_size=4, num_blocks=8, prefill_chunk=8)),
]


@pytest.mark.parametrize("scenario,kw", SHARING_PARITY_CASES,
                         ids=[c[0] for c in SHARING_PARITY_CASES])
def test_greedy_parity_with_prefix_sharing_enabled(scenario, kw):
    mesh = make_host_mesh()
    eng, got = _run_scenario(scenario, mesh, share_prefix=True, **kw)
    assert got == load_goldens(scenario), scenario
    if scenario.endswith("preempt"):
        # the victim was a sharing request: its committed blocks retired to
        # the LRU and re-matched when it was re-admitted
        assert eng.metrics.preemptions > 0
        assert eng.cache.prefix_stats()["hit_tokens"] > 0
    # after drain no request holds blocks; only the content index does
    assert eng.cache.allocator.num_used == eng.cache.num_cached
    assert eng.metrics.summary()["prefix_hit_rate"] \
        == pytest.approx(eng.cache.prefix_stats()["hit_rate"])


def test_shared_prefix_skips_prefill_and_matches_unshared_outputs():
    """Requests sharing a system-prompt prefix: admission hands the second
    request the first's cached blocks and starts prefill at the matched
    boundary, and greedy outputs are identical to the sharing-off serve."""
    mesh = make_host_mesh()
    prefix = np.arange(1, 13, dtype=np.int32)       # 3 full blocks of 4
    prompts = [np.concatenate([prefix, np.asarray([50 + i, 60 + i],
                                                  np.int32)])
               for i in range(4)]

    def serve(share):
        eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh,
                                       slots=2, max_len=64, block_size=4,
                                       prefill_chunk=4, share_prefix=share)
        for i, p in enumerate(prompts):
            eng.submit(Request(id=i, prompt=p.copy(), max_new_tokens=5))
        eng.run_until_drained()
        return eng, {o.request_id: o.token_ids for o in eng.completed}

    eng_off, out_off = serve(False)
    eng_on, out_on = serve(True)
    assert out_on == out_off
    stats = eng_on.cache.prefix_stats()
    # requests 0 and 1 fill both slots in the same admission step, before
    # either commits a block, so they prefill privately (first writer wins
    # the index); 2 and 3 match the full prefix
    assert stats["hit_tokens"] == 2 * len(prefix)
    assert eng_off.cache.prefix_stats()["hit_tokens"] == 0
    # the skipped prefix means fewer prefill chunks end to end
    assert eng_on.metrics.prefill_chunks < eng_off.metrics.prefill_chunks


def test_shared_prefix_admission_starts_at_matched_boundary():
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=4,
                                   share_prefix=True)
    prefix = np.arange(1, 9, dtype=np.int32)        # 2 full blocks
    eng.submit(Request(id=0, prompt=prefix.copy(), max_new_tokens=2))
    eng.run_until_drained()
    eng.submit(Request(id=1,
                       prompt=np.concatenate([prefix, [77]]).astype(np.int32),
                       max_new_tokens=2))
    eng._admit()
    slot = next(s for s in eng.slots if s.busy)
    assert slot.prefill_pos == 8                    # prefill skips the prefix
    assert slot.pos == 8
    eng.run_until_drained()
    assert len(eng.completed) == 2


def test_parity_with_multiple_victims_in_one_step():
    """Regression: a slot preempted as a victim for an earlier slot's block
    grab must be skipped by the rest of that decode step (slot.req is None).
    4 decoding slots x 2 blocks each > 6 usable blocks forces it."""
    mesh = make_host_mesh()
    eng, got = _run_scenario("tiny/victims", mesh, block_size=16,
                             num_blocks=7, prefill_chunk=16)
    assert got == load_goldens("tiny/victims")
    assert eng.metrics.preemptions > 0


def test_parity_with_mixed_max_new_tokens():
    """Regression: with mixed max_new the longer request must truncate at
    max_len exactly where the wave Server did (golden req 1: 4 of its 20
    requested tokens at max_len=12)."""
    mesh = make_host_mesh()
    _, got = _run_scenario("tiny/mixed", mesh, block_size=4, prefill_chunk=8)
    want = load_goldens("tiny/mixed")
    assert len(want[1]) == 4                  # truncated: 12 - 8
    assert got == want


def test_prefill_serves_oldest_request_first():
    """Regression: chunked prefill must advance the oldest admitted request
    (scheduler FCFS seq), not the lowest slot index."""
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=2)
    older = Request(id=0, prompt=np.arange(1, 9, dtype=np.int32))
    newer = Request(id=1, prompt=np.arange(1, 9, dtype=np.int32))
    eng.submit(older)
    eng.submit(newer)
    eng._admit()
    # simulate slot churn: the older request ends up in the *higher* slot
    eng.slots[0], eng.slots[1] = eng.slots[1], eng.slots[0]
    assert eng.slots[0].req.req is newer and eng.slots[1].req.req is older
    eng._prefill_chunk()
    assert eng.slots[1].prefill_pos == 2      # older advanced
    assert eng.slots[0].prefill_pos == 0      # newer waits


# ---------------------------------------------------------------------------
# per-request frontends consumed once at admission
# ---------------------------------------------------------------------------

def test_cross_kv_computed_once_at_admission():
    """A request carrying frontend embeddings gets its cross K/V projected
    into its slot rows at admit time; with nonzero attention gates the
    frontend changes the greedy output vs the text-only (zero cross-K/V)
    serve."""
    mesh = make_host_mesh()
    params = T.init_lm(jax.random.PRNGKey(0), TINY_CROSS)
    # llama-vision tanh gates init at 0 => open them so cross-attn matters
    for si, seg in enumerate(TINY_CROSS.pattern):
        blk = params["segments"][si]["b1"]
        blk["attn"]["gate"] = jnp.ones_like(blk["attn"]["gate"])
        blk["mlp_gate"] = jnp.ones_like(blk["mlp_gate"])
    fe = np.asarray(20 * jax.random.normal(jax.random.PRNGKey(3), (1, 8, 64)),
                    np.float32)
    prompt = np.arange(1, 7, dtype=np.int32)

    def serve(frontend):
        eng = ContinuousBatchingEngine(TINY_CROSS, params, mesh, slots=2,
                                       max_len=32, block_size=4,
                                       prefill_chunk=4)
        eng.submit(Request(id=0, prompt=prompt.copy(), max_new_tokens=4,
                           frontend=frontend))
        eng.run_until_drained()
        return eng, eng.completed[0].token_ids

    eng, with_fe = serve(fe)
    # slot 0's cross-K row equals the direct projection of the frontend
    from repro.models import blocks as B
    cfg = B.attn_cfg_for(TINY_CROSS, causal=False, gated=True,
                         use_rope=False)
    attn0 = jax.tree.map(lambda t: t[0], params["segments"][0]["b1"]["attn"])
    k_ref = L.dense(attn0["wk"], jnp.asarray(fe[0])).reshape(
        8, cfg.n_kv_heads, cfg.head_dim)
    got = np.asarray(eng.cache.pools[0]["b1"]["k"][0, 0])
    np.testing.assert_allclose(got, np.asarray(k_ref), rtol=1e-6)
    _, text_only = serve(None)
    assert with_fe != text_only


def test_whisper_encoder_runs_once_at_admission():
    """An audio request's frame embeddings run through the encoder stack
    exactly once, at admission: the resulting cross K/V lands in the slot's
    wdec rows (exact content check), and the decoder's logits demonstrably
    read those rows (they shift vs the text-only zero-K/V serve — the tiny
    model's layernormed encoder output is O(1), so asserting on logits, not
    argmax, keeps the check robust)."""
    mesh = make_host_mesh()
    params = _params_for(TINY_ENCDEC)
    enc_len = TINY_ENCDEC.encoder.seq_len
    fe = np.asarray(20 * jax.random.normal(jax.random.PRNGKey(5),
                                           (1, enc_len, 64)), np.float32)
    prompt = np.arange(1, 7, dtype=np.int32)

    # a logits-returning (un-fused) prefill step: the engine's own
    # _prefill now samples on device and returns tokens, not logits
    raw_prefill = jax.jit(ST.make_paged_prefill_step(TINY_ENCDEC))

    def logits_after_admit(frontend):
        """Admit (encoder runs here, once), snapshot slot 0's cross-K row,
        then run a raw prefill step on the post-admission pools and return
        its logits."""
        eng = ContinuousBatchingEngine(TINY_ENCDEC, params, mesh, slots=2,
                                       max_len=32, block_size=4,
                                       prefill_chunk=8)
        eng.submit(Request(id=0, prompt=prompt.copy(), max_new_tokens=4,
                           frontend=frontend))
        eng._admit()
        k_row = np.asarray(eng.cache.pools[0]["b0"]["cross"]["k"][0, 0])
        slot = eng.slots[0]
        ctx = slot.req.context()
        chunk = np.concatenate([ctx, np.zeros(8 - len(ctx), np.int32)])
        table = eng.cache.table_array([slot.req.id])
        logits, eng.cache.pools = raw_prefill(
            eng.params, eng.cache.pools, jnp.asarray(chunk[None, :]),
            jnp.asarray([0], jnp.int32), jnp.asarray(table),
            jnp.asarray([len(ctx)], jnp.int32),
            jnp.asarray([slot.idx], jnp.int32))
        return k_row, np.asarray(logits)

    k_row, with_fe = logits_after_admit(fe)
    # slot 0's cross-K rows equal projecting the encoder output directly
    from repro.models import blocks as B
    enc_out = T.encode_frontend(params, TINY_ENCDEC, jnp.asarray(fe))[0]
    cfg = B.attn_cfg_for(TINY_ENCDEC, causal=False, use_rope=False)
    x0 = jax.tree.map(lambda t: t[0], params["segments"][0]["b0"]["xattn"])
    k_ref = L.dense(x0["wk"], enc_out).reshape(enc_len, cfg.n_kv_heads,
                                               cfg.head_dim)
    np.testing.assert_allclose(k_row, np.asarray(k_ref), rtol=1e-5,
                               atol=1e-5)
    _, text_only = logits_after_admit(None)
    assert np.abs(with_fe - text_only).max() > 0.1   # decoder reads the rows


# ---------------------------------------------------------------------------
# submit-time validation
# ---------------------------------------------------------------------------

def test_submit_rejects_duplicate_ids_and_empty_prompts():
    """Regression: block tables are keyed by request id, so a duplicate
    in-flight id silently shared (and corrupted) the live request's table;
    an empty prompt crashed the prefill with a KeyError.  Both must be
    rejected at submit; a finished id may be reused."""
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8)
    eng.submit(Request(id=7, prompt=np.arange(1, 5, dtype=np.int32),
                       max_new_tokens=2))
    with pytest.raises(ValueError, match="already in flight"):
        eng.submit(Request(id=7, prompt=np.arange(1, 5, dtype=np.int32),
                           max_new_tokens=2))
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(id=8, prompt=np.array([], np.int32)))
    eng.run_until_drained()
    eng.submit(Request(id=7, prompt=np.arange(1, 5, dtype=np.int32),
                       max_new_tokens=2))         # id free again after finish
    eng.run_until_drained()
    assert len(eng.completed) == 2


def test_submit_rejects_zero_max_new_tokens():
    """Regression: a max_new_tokens=0 request still generated one token —
    the prefill path unconditionally samples after the final chunk.  Policy:
    reject at submit."""
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8)
    for bad in (0, -3):
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(Request(id=1, prompt=np.arange(1, 5, dtype=np.int32),
                               max_new_tokens=bad))
    assert not eng.has_work                   # nothing was enqueued


def test_request_is_input_only_and_resubmittable():
    """v2 semantics: the engine never mutates a Request (results come back
    as RequestOutput), so a finished Request object may be resubmitted
    verbatim — the v1 recycled-object hazard (stale out_tokens re-prefilled
    as context, stale _sched_seq jumping the FCFS queue) cannot exist."""
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8)
    req = Request(id=0, prompt=np.arange(1, 5, dtype=np.int32),
                  max_new_tokens=2)
    eng.submit(req)
    eng.run_until_drained()
    assert not hasattr(req, "out_tokens") and not hasattr(req, "done")
    assert req.__dict__.get("_sched_seq") is None   # no bookkeeping stuck on
    eng.submit(req)                                 # same object, second pass
    eng.run_until_drained()
    assert len(eng.completed) == 2
    a, b = eng.completed
    assert a.request_id == b.request_id == 0
    assert a.token_ids == b.token_ids               # deterministic greedy


# ---------------------------------------------------------------------------
# numerics regressions
# ---------------------------------------------------------------------------

def test_short_prompt_mamba2_handoff():
    """Regression: a prompt shorter than d_conv-1 used to under-fill the
    conv buffer at the prefill->decode handoff (xr[:, -K:, :] yields < K
    rows).  A 1-token prompt must decode, and greedily continuing from a
    2-token prompt must reproduce the same stream (exact handoff state)."""
    mesh = make_host_mesh()
    params = _params_for(TINY_SSM)

    def serve(prompt, max_new):
        eng = ContinuousBatchingEngine(TINY_SSM, params, mesh, slots=1,
                                       max_len=32, block_size=4,
                                       prefill_chunk=4)
        eng.submit(Request(id=0, prompt=prompt, max_new_tokens=max_new))
        eng.run_until_drained()
        return eng.completed[0].token_ids

    first = serve(np.array([5], np.int32), 6)
    assert len(first) == 6
    cont = serve(np.array([5, first[0]], np.int32), 5)
    assert cont == first[1:]


def test_paged_attention_overrun_diverts_to_null_block():
    """Regression: a write past a request's block-table capacity used to be
    clamped into its *last* block, corrupting live KV.  Overrun writes must
    land in the null block and leave every live block (and prior-token
    logits) bit-identical."""
    cfg = L.AttnConfig(d_model=32, n_heads=2, n_kv_heads=2, head_dim=16)
    p = L.init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    BS, NB = 4, 6
    pool = L.init_paged_attention_cache(cfg, NB, BS, jnp.float32)
    xa = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32))
    ta = jnp.asarray([[1, 2]], jnp.int32)          # capacity: 8 tokens
    _, pool = L.paged_attention(p, cfg, xa, cache=pool,
                                positions=jnp.array([0]), block_tables=ta)
    out1, pool1 = L.paged_attention(p, cfg, xa[:, -1:], cache=pool,
                                    positions=jnp.array([7]),
                                    block_tables=ta)
    # another request writes OUT of table: position 9 -> logical block 2
    xb = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 32))
    _, pool2 = L.paged_attention(p, cfg, xb, cache=pool1,
                                 positions=jnp.array([9]),
                                 block_tables=jnp.asarray([[3, 4]],
                                                          jnp.int32))
    perturbed = [b for b in range(NB)
                 if not np.array_equal(np.asarray(pool1["k"][b]),
                                       np.asarray(pool2["k"][b]))]
    assert perturbed in ([], [0])                  # only the null block
    out2, _ = L.paged_attention(p, cfg, xa[:, -1:], cache=pool2,
                                positions=jnp.array([7]), block_tables=ta)
    assert np.array_equal(np.asarray(out1), np.asarray(out2))


def test_sinusoidal_odd_d_model():
    """Regression: odd d_model used to raise a shape error (floor(d/2) cos
    columns assigned ceil(d/2) values)."""
    for d in (5, 7, 64):
        pe = T.sinusoidal_at(jnp.arange(6), d)
        assert pe.shape == (6, d)
    # even path unchanged: interleaved sin/cos
    pe = T.sinusoidal_at(jnp.arange(4), 6)
    np.testing.assert_allclose(np.asarray(pe[:, 0]),
                               np.sin(np.arange(4, dtype=np.float32)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# multi-host decode (ROADMAP precondition (b))
# ---------------------------------------------------------------------------

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(run by the serving-multihost CI job)")


@needs_8_devices
def test_multihost_decode_parity_and_cache_placement():
    """Sharded serving proof on an 8-device (data=4, model=2) host mesh:
    greedy decode stays token-identical to the single-device wave goldens,
    and every paged/slot-state pool actually lands on the axes its
    SchedulePlan.paged_cache_specs() declares (at least one pool leaf
    genuinely sharded over `model`, not everything silently replicated)."""
    from jax.sharding import NamedSharding
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         (AxisType.Auto, AxisType.Auto))
    sharded_leaves = 0
    for scenario, kw in [("tiny/base", dict(block_size=4, prefill_chunk=3)),
                         ("hybrid/base", dict(block_size=4, prefill_chunk=4)),
                         ("mla/base", dict(block_size=4, prefill_chunk=3))]:
        arch, reqs, slots, max_len = scenario_requests(scenario)
        eng = ContinuousBatchingEngine(arch, _params_for(arch), mesh,
                                       slots=slots, max_len=max_len, **kw)
        specs = eng.plan.paged_cache_specs()
        pool_leaves = jax.tree.leaves(eng.cache.pools)
        spec_leaves = jax.tree.leaves(specs)
        assert len(pool_leaves) == len(spec_leaves)
        for leaf, spec in zip(pool_leaves, spec_leaves):
            want = NamedSharding(mesh, spec)
            assert leaf.sharding.is_equivalent_to(want, leaf.ndim), \
                (scenario, spec, leaf.sharding)
            if any(ax is not None for ax in spec):
                sharded_leaves += 1
        for rid, prompt, max_new in reqs:
            eng.submit(Request(id=rid, prompt=prompt.copy(),
                               max_new_tokens=max_new))
        eng.run_until_drained()
        got = {o.request_id: o.token_ids for o in eng.completed}
        assert got == load_goldens(scenario), scenario
    assert sharded_leaves > 0


@needs_8_devices
def test_multihost_parity_under_preemption():
    """Recompute-preemption on the sharded mesh: release/re-admit must not
    perturb pool placement or greedy outputs."""
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         (AxisType.Auto, AxisType.Auto))
    arch, reqs, slots, max_len = scenario_requests("hybrid/preempt")
    eng = ContinuousBatchingEngine(arch, _params_for(arch), mesh,
                                   slots=slots, max_len=max_len,
                                   block_size=4, num_blocks=8,
                                   prefill_chunk=8)
    for rid, prompt, max_new in reqs:
        eng.submit(Request(id=rid, prompt=prompt.copy(),
                           max_new_tokens=max_new))
    eng.run_until_drained()
    assert {o.request_id: o.token_ids for o in eng.completed} \
        == load_goldens("hybrid/preempt")
    assert eng.metrics.preemptions > 0


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_json_report():
    m = ServingMetrics()
    m.on_submit(0, now=0.0)
    m.on_first_token(0, now=0.5)
    m.on_first_token(0, now=9.9)              # resumed request: TTFT kept
    m.on_step(queue_depth=1, busy_slots=1, slots=2)
    m.on_finish(0, n_tokens=3, now=1.5)
    rep = json.loads(m.to_json(engine="continuous"))
    assert rep["engine"] == "continuous"
    assert rep["completed"] == 1 and rep["total_tokens"] == 3
    assert rep["in_flight"] == 0
    assert rep["requests"][0]["ttft_s"] == pytest.approx(0.5)
    assert rep["requests"][0]["tpot_s"] == pytest.approx(0.5)  # 1.0s / 2
    assert rep["tokens_per_sec"] == pytest.approx(2.0)         # 3 tok / 1.5s
    assert rep["slot_occupancy_mean"] == pytest.approx(0.5)
    for key in ("ttft_mean_s", "tpot_mean_s", "queue_depth_max",
                "preemptions", "decode_steps"):
        assert key in rep


def test_metrics_preempted_request_keeps_original_ttft():
    """A preempted-then-finished request reports the TTFT of its FIRST
    first-token, not the resume's — preemption may not launder latency."""
    m = ServingMetrics()
    m.on_submit(0, now=0.0)
    m.on_first_token(0, now=0.4)
    m.on_preempt(0)
    m.on_first_token(0, now=5.0)              # re-prefill samples again
    m.on_finish(0, n_tokens=6, now=6.0)
    rep = m.request_report(0)
    assert rep["ttft_s"] == pytest.approx(0.4)
    assert m.preemptions == 1
    # TPOT spans first token -> finish: (6.0 - 0.4) / (6 - 1)
    assert rep["tpot_s"] == pytest.approx(5.6 / 5)


def test_metrics_single_token_request_tpot():
    """n_tokens=1 has no post-first-token decode: TPOT must not divide by
    zero, and equals the (zero-length) decode span."""
    m = ServingMetrics()
    m.on_submit(0, now=0.0)
    m.on_first_token(0, now=0.3)
    m.on_finish(0, n_tokens=1, now=0.3)
    rep = m.request_report(0)
    assert rep["tpot_s"] == pytest.approx(0.0)
    assert rep["ttft_s"] == pytest.approx(0.3)


def test_metrics_in_flight_requests_report_none_not_negative():
    """Regression: request_report defaulted missing timestamps to 0.0, so a
    submitted-not-started request reported ttft_s = -submit_t (large and
    negative) and a started-not-finished one a negative tpot_s.  Missing
    lifecycle points must yield None, and summary() means must skip them."""
    m = ServingMetrics()
    m.on_submit(0, now=100.0)                 # submitted, no first token yet
    rep = m.request_report(0)
    assert rep["ttft_s"] is None and rep["tpot_s"] is None
    m.on_submit(1, now=100.0)                 # started, not finished
    m.on_first_token(1, now=100.5)
    rep = m.request_report(1)
    assert rep["ttft_s"] == pytest.approx(0.5)
    assert rep["tpot_s"] is None
    # an id never submitted at all
    rep = m.request_report(99)
    assert rep["ttft_s"] is None and rep["tpot_s"] is None
    # summary stays total: latencies that exist are aggregated (request 1's
    # TTFT is known even though it hasn't finished), missing ones are
    # skipped rather than fabricated
    m.on_submit(2, now=101.0)
    m.on_first_token(2, now=101.2)
    m.on_finish(2, n_tokens=3, now=102.2)
    s = m.summary()
    assert s["ttft_mean_s"] == pytest.approx((0.5 + 0.2) / 2)
    assert s["tpot_mean_s"] == pytest.approx(0.5)
    assert s["in_flight"] == 2                # requests 0 and 1 still going


def test_metrics_block_utilization_and_prefix_hit_rate():
    """Cache pressure is sampled per step (block_utilization_mean/max) and
    prefix-cache admission matches aggregate into prefix_hit_rate."""
    m = ServingMetrics()
    m.on_step(0, 1, 2, block_utilization=0.25)
    m.on_step(0, 2, 2, block_utilization=0.75)
    m.on_step(0, 2, 2)                        # engines without a sample
    m.on_prefix_match(12, 16)
    m.on_prefix_match(0, 8)
    s = m.summary()
    assert s["block_utilization_mean"] == pytest.approx(0.5)
    assert s["block_utilization_max"] == pytest.approx(0.75)
    assert s["prefix_hit_rate"] == pytest.approx(12 / 24)
    assert ServingMetrics().summary()["prefix_hit_rate"] == 0.0


def test_engine_samples_block_utilization():
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8)
    eng.submit(Request(id=0, prompt=np.arange(1, 9, dtype=np.int32),
                       max_new_tokens=4))
    eng.run_until_drained()
    s = eng.metrics.summary()
    assert eng.metrics.block_utilization.count == s["engine_steps"]
    assert s["block_utilization_max"] > 0.0


def test_run_until_drained_raises_instead_of_spinning():
    """A wedged engine (work queued, nothing running, admission refusing
    forever) must raise after max_idle_steps, not spin silently."""
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(
        TINY, _params_for(TINY), mesh, slots=2, max_len=64, block_size=4,
        prefill_chunk=8,
        scheduler=RequestScheduler(max_tokens_in_flight=64))
    eng.submit(Request(id=0, prompt=np.arange(1, 9, dtype=np.int32),
                       max_new_tokens=4))
    # simulate a leaked budget: admission is refused forever while the
    # queue stays non-empty and every slot is idle
    eng.scheduler._in_flight_tokens = 64
    with pytest.raises(RuntimeError, match="no progress"):
        eng.run_until_drained(max_idle_steps=10)
    # a healthy engine drains fine under the same guard
    eng.scheduler._in_flight_tokens = 0
    eng.run_until_drained(max_idle_steps=10)
    assert len(eng.completed) == 1


def test_metrics_summary_on_empty_and_partial_runs():
    """summary() must be total (no ZeroDivision / max-of-empty) on a fresh
    collector and on a run with submitted-but-unfinished requests."""
    m = ServingMetrics()
    s = m.summary()
    assert s["completed"] == 0 and s["total_tokens"] == 0
    # "no data" is None, not a 0.0 that reads as infinitely-fast/empty
    assert s["tokens_per_sec"] is None and s["ttft_max_s"] is None
    assert s["queue_depth_max"] is None and s["requests"] == []
    # partial: one finished, one still in flight — BOTH must appear in the
    # report (in-flight ids used to vanish because requests iterated
    # finish_t only), with the unfinished one counted as in_flight and its
    # latencies None
    m.on_submit(0, now=0.0)
    m.on_submit(1, now=0.0)
    m.on_first_token(0, now=0.2)
    m.on_finish(0, n_tokens=2, now=0.5)
    s = m.summary()
    assert s["completed"] == 1 and s["in_flight"] == 1
    assert [r["id"] for r in s["requests"]] == [0, 1]
    assert s["requests"][1]["ttft_s"] is None
    assert s["requests"][1]["tpot_s"] is None
    assert s["total_tokens"] == 2
    # means stay unpolluted by the in-flight request's None latencies
    assert s["ttft_mean_s"] == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# generation API v2: SamplingParams, seeded stochastic decode, stop
# conditions, typed RequestOutput, generate/stream/on_token
# ---------------------------------------------------------------------------

def test_sampling_params_validated_at_submit():
    """Malformed decode controls must be rejected at submit (with the
    request id in the error), never reach a jitted step, and leave the
    engine empty."""
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8)
    bad = [SamplingParams(temperature=-0.5),
           SamplingParams(temperature=float("nan")),
           SamplingParams(top_k=-1),
           SamplingParams(top_k=TINY.vocab + 1),
           SamplingParams(top_p=0.0),
           SamplingParams(top_p=1.5),
           SamplingParams(seed=-1),
           SamplingParams(seed=2 ** 32),
           SamplingParams(stop_token_ids=(TINY.vocab,)),
           SamplingParams(stop_token_ids=(-3,))]
    for sp in bad:
        with pytest.raises(ValueError, match="request 5"):
            eng.submit(Request(id=5, prompt=np.arange(1, 5, dtype=np.int32),
                               sampling=sp))
    assert not eng.has_work
    # the same checks are usable standalone
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=2.0).validate()
    SamplingParams(temperature=0.7, top_k=10, top_p=0.9, seed=3,
                   stop_token_ids=(1, 2), logprobs=True).validate(TINY.vocab)


def test_temperature_zero_ignores_other_knobs_and_matches_goldens():
    """temperature=0 through explicit SamplingParams lowers to exact argmax
    regardless of top_k/top_p/seed — bit parity with the greedy goldens,
    including under forced preemption."""
    mesh = make_host_mesh()
    sp = SamplingParams(temperature=0.0, top_k=3, top_p=0.4, seed=1234)
    for scenario, kw in [("tiny/base", dict(block_size=4, prefill_chunk=3)),
                         ("hybrid/preempt", dict(block_size=4, num_blocks=8,
                                                 prefill_chunk=8)),
                         ("mla/preempt", dict(block_size=4, num_blocks=8,
                                              prefill_chunk=8))]:
        eng, got = _run_scenario(scenario, mesh, sampling=sp, **kw)
        assert got == load_goldens(scenario), scenario
        if scenario.endswith("preempt"):
            assert eng.metrics.preemptions > 0


def test_top_k_one_is_greedy_at_any_temperature():
    """End-to-end mask check: top_k=1 collapses the candidate set to the
    argmax, so even a hot temperature must reproduce the greedy goldens."""
    mesh = make_host_mesh()
    sp = SamplingParams(temperature=1.5, top_k=1, seed=7)
    _, got = _run_scenario("tiny/base", mesh, sampling=sp,
                           block_size=4, prefill_chunk=3)
    assert got == load_goldens("tiny/base")


def test_sampled_decode_deterministic_and_seed_sensitive():
    """Same seed => bit-identical reruns; different seed => a different
    stream (vocab 256, 8 tokens — collision odds are negligible); logprobs
    are per-token, finite and <= 0."""
    mesh = make_host_mesh()

    def run(seed):
        eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh,
                                       slots=2, max_len=64, block_size=4,
                                       prefill_chunk=3)
        sp = SamplingParams(temperature=0.9, top_k=50, top_p=0.9, seed=seed,
                            logprobs=True)
        return eng.generate([Request(id=0,
                                     prompt=np.arange(1, 9, dtype=np.int32),
                                     max_new_tokens=8, sampling=sp)])[0]

    a, b, c = run(123), run(123), run(321)
    assert a.token_ids == b.token_ids
    assert a.logprobs == b.logprobs
    assert a.token_ids != c.token_ids
    assert a.finish_reason == "length" and a.n_tokens == 8
    assert len(a.logprobs) == 8
    assert all(np.isfinite(lp) and lp <= 0 for lp in a.logprobs)
    # greedy requests don't carry logprobs unless asked
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=3)
    out = eng.generate([Request(id=0, prompt=np.arange(1, 9, dtype=np.int32),
                                max_new_tokens=4)])[0]
    assert out.logprobs is None and out.prompt_len == 8


def test_sampled_determinism_under_forced_preemption():
    """The acceptance property: a seeded temperature>0 run is bit-identical
    with and without forced recompute-preemption of the sampling requests —
    keys derive from (seed, absolute position) only, so a preempted
    request's re-prefill regenerates exactly the tokens it lost."""
    mesh = make_host_mesh()

    def run(**kw):
        eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh,
                                       slots=2, max_len=64, prefill_chunk=8,
                                       block_size=4, **kw)
        reqs = [Request(id=i, prompt=np.arange(1, 9, dtype=np.int32) + i,
                        max_new_tokens=8,
                        sampling=SamplingParams(temperature=0.8, top_p=0.95,
                                                seed=100 + i))
                for i in range(4)]
        return eng, {o.request_id: o.token_ids for o in eng.generate(reqs)}

    eng_ample, ample = run()
    eng_tight, tight = run(num_blocks=8)         # forces preemption
    assert eng_ample.metrics.preemptions == 0
    assert eng_tight.metrics.preemptions > 0
    assert tight == ample


def test_sampled_preemption_rematches_prefix_cache_blocks():
    """With share_prefix, a preempted sampling request must re-match its
    own retired blocks at re-admission — only possible because the
    regenerated tokens are identical, keeping the block hash chain
    stable."""
    mesh = make_host_mesh()

    def run(**kw):
        eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh,
                                       slots=2, max_len=64, prefill_chunk=8,
                                       block_size=4, share_prefix=True, **kw)
        reqs = [Request(id=i, prompt=np.arange(1, 9, dtype=np.int32) + i,
                        max_new_tokens=8,
                        sampling=SamplingParams(temperature=0.8, seed=7 + i))
                for i in range(4)]
        return eng, {o.request_id: o.token_ids for o in eng.generate(reqs)}

    eng_tight, tight = run(num_blocks=8)
    eng_ample, ample = run()
    assert eng_tight.metrics.preemptions > 0
    assert eng_tight.cache.prefix_stats()["hit_tokens"] > 0
    assert tight == ample


def test_sampled_neighbor_does_not_perturb_greedy_requests():
    """Per-slot parameter isolation: a hot-temperature request sharing the
    batch (and fighting for the same blocks) must not change its greedy
    neighbors' tokens — their outputs are position-pure functions of their
    own context and must still match the goldens."""
    mesh = make_host_mesh()
    arch, reqs, slots, max_len = scenario_requests("tiny/base")
    eng = ContinuousBatchingEngine(arch, _params_for(arch), mesh,
                                   slots=slots, max_len=max_len,
                                   block_size=4, prefill_chunk=3)
    outs = eng.generate([
        Request(id=rid, prompt=prompt.copy(), max_new_tokens=max_new,
                sampling=(SamplingParams(temperature=1.2, seed=5)
                          if rid == 1 else SamplingParams()))
        for rid, prompt, max_new in reqs])
    want = load_goldens("tiny/base")
    for o in outs:
        if o.request_id == 1:
            assert o.token_ids != want[1]        # it really sampled
        else:
            assert o.token_ids == want[o.request_id]


def test_stop_token_finishes_with_reason_and_budget_release():
    """Sampling a stop token finishes the request with
    finish_reason="stop" (the stop token is the last entry of token_ids),
    releases its cache blocks AND its scheduler token-budget charge — a
    budget sized for one request must admit the next one only because the
    stop cut the first short."""
    mesh = make_host_mesh()
    want = load_goldens("tiny/base")[0]          # greedy stream for prompt 0
    stop_tok = want[2]
    sched = RequestScheduler(max_tokens_in_flight=14)   # one 8+6 request
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=3,
                                   scheduler=sched)
    prompt = np.arange(1, 9, dtype=np.int32)
    outs = eng.generate([
        Request(id=0, prompt=prompt.copy(), max_new_tokens=6,
                sampling=SamplingParams(stop_token_ids=(stop_tok,))),
        Request(id=1, prompt=prompt.copy(), max_new_tokens=6)])
    assert outs[0].finish_reason == "stop"
    assert outs[0].token_ids == want[:3]         # truncated at the stop hit
    assert outs[1].finish_reason == "length"
    assert outs[1].token_ids == want             # same prompt, full stream
    assert sched._in_flight_tokens == 0          # charges fully released
    assert eng.cache.allocator.num_used == 0
    # a stop token the stream never samples is inert
    eng2 = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                    max_len=64, block_size=4, prefill_chunk=3)
    out = eng2.generate([Request(
        id=0, prompt=prompt.copy(), max_new_tokens=6,
        sampling=SamplingParams(stop_token_ids=(stop_tok + 1,)))])[0]
    assert out.finish_reason == "length" and out.token_ids == want


def test_stop_token_on_first_token_finishes_in_prefill():
    """A stop token sampled as the very first token finishes the request
    straight out of prefill — it never enters decode."""
    mesh = make_host_mesh()
    want = load_goldens("tiny/base")[0]
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=3)
    out = eng.generate([Request(
        id=0, prompt=np.arange(1, 9, dtype=np.int32), max_new_tokens=6,
        sampling=SamplingParams(stop_token_ids=(want[0],)))])[0]
    assert out.finish_reason == "stop" and out.token_ids == want[:1]
    assert eng.metrics.decode_steps == 0


def test_top_p_mask_keeps_mass_and_never_empties():
    """Property test for the nucleus mask: over random logit rows and
    top_p values, the kept set (finite entries) is never empty, its
    probability mass is >= top_p, and it is minimal — dropping its least
    probable member would fall below top_p."""
    rng = np.random.default_rng(0)
    for trial in range(25):
        b, v = 8, 64
        logits = jnp.asarray(rng.normal(0, 3, size=(b, v)), jnp.float32)
        top_p = jnp.asarray(rng.uniform(0.05, 1.0, size=(b,)), jnp.float32)
        masked = np.asarray(apply_top_p(logits, top_p))
        probs = np.asarray(jax.nn.softmax(logits, axis=-1))
        for i in range(b):
            kept = np.isfinite(masked[i])
            assert kept.any()                       # never empty
            mass = probs[i][kept].sum()
            assert mass >= float(top_p[i]) - 1e-6   # >= p mass kept
            if kept.sum() > 1:                      # minimal
                assert mass - probs[i][kept].min() < float(top_p[i]) + 1e-6
    # top_p = 1.0 keeps the whole (finite) vocabulary
    full = np.asarray(apply_top_p(jnp.zeros((1, 8)), jnp.ones((1,))))
    assert np.isfinite(full).all()


def test_top_k_mask_keeps_exactly_k():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)  # no ties a.s.
    for k, want in [(1, 1), (5, 5), (32, 32), (0, 32)]:   # 0 disables
        masked = np.asarray(apply_top_k(logits, jnp.full((4,), k, jnp.int32)))
        assert (np.isfinite(masked).sum(axis=-1) == want).all()
        # the survivors are the k largest
        for i in range(4):
            kept = set(np.flatnonzero(np.isfinite(masked[i])))
            top = set(np.argsort(np.asarray(logits[i]))[-(k or 32):])
            assert kept == top
    # top-p composes after top-k: mass is renormalized over the survivors
    masked = apply_top_k(logits, jnp.full((4,), 4, jnp.int32))
    both = np.asarray(apply_top_p(masked, jnp.full((4,), 0.5, jnp.float32)))
    assert (np.isfinite(both).sum(axis=-1) <= 4).all()
    assert (np.isfinite(both).sum(axis=-1) >= 1).all()


def test_generate_returns_outputs_in_submission_order():
    """generate() orders results by the request list, not by finish order
    (the short request finishes long before the 20-token one)."""
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8)
    prompt = np.arange(1, 9, dtype=np.int32)
    outs = eng.generate([
        Request(id=10, prompt=prompt.copy(), max_new_tokens=20),
        Request(id=11, prompt=prompt.copy(), max_new_tokens=2)])
    assert [o.request_id for o in outs] == [10, 11]
    assert [o.n_tokens for o in outs] == [20, 2]
    # finish order on the engine's completed list was the reverse
    assert [o.request_id for o in eng.completed] == [11, 10]


def test_stream_and_on_token_fire_per_sampled_token():
    """stream() yields (request_id, token) pairs in sampling order and
    composes with a caller-installed on_token; the reassembled streams
    equal the final RequestOutputs."""
    mesh = make_host_mesh()
    cb: list = []
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=3,
                                   on_token=lambda rid, tok: cb.append((rid,
                                                                        tok)))
    prompt = np.arange(1, 9, dtype=np.int32)
    reqs = [Request(id=i, prompt=prompt.copy() + i, max_new_tokens=4)
            for i in range(3)]
    pairs = list(eng.stream(reqs))
    assert pairs == cb                      # tap preserved the user callback
    assert eng.on_token is not None         # and restored it afterwards
    streams: dict = {}
    for rid, tok in pairs:
        streams.setdefault(rid, []).append(tok)
    assert streams == {o.request_id: o.token_ids for o in eng.completed}
    assert len(eng.completed) == 3


def test_engine_clock_injection_keeps_latencies_coherent():
    """Satellite regression: submit used to accept a synthetic `now` while
    _prefill_chunk/_finish stamped real perf_counter() times, fabricating
    TTFTs of ~perf_counter magnitude.  With the injected clock every
    lifecycle stamp shares one time source."""
    mesh = make_host_mesh()
    t = {"now": 1000.0}

    def clock():
        t["now"] += 1.0                      # one tick per lifecycle stamp
        return t["now"]

    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8,
                                   clock=clock)
    eng.submit(Request(id=0, prompt=np.arange(1, 9, dtype=np.int32),
                       max_new_tokens=4))    # stamped by the fake clock too
    eng.run_until_drained()
    out = eng.completed[0]
    rep = eng.metrics.request_report(0)
    # every latency is a small positive number of fake ticks — mixing in a
    # real perf_counter() would make TTFT ~1e3 negative or ~1e5 positive
    assert 0 < rep["ttft_s"] < 100 and 0 < rep["tpot_s"] < 100
    assert out.ttft_s == rep["ttft_s"] and out.tpot_s == rep["tpot_s"]
    s = eng.metrics.summary()
    assert 0 < s["ttft_mean_s"] < 100
    assert s["in_flight"] == 0


def test_request_output_latency_joined_from_metrics():
    """RequestOutput carries the same TTFT/TPOT the metrics report — one
    join at finish time, no second bookkeeping path."""
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8)
    outs = eng.generate([Request(id=i,
                                 prompt=np.arange(1, 9, dtype=np.int32),
                                 max_new_tokens=3) for i in [4, 9]])
    for o in outs:
        rep = eng.metrics.request_report(o.request_id)
        assert o.ttft_s == rep["ttft_s"] is not None
        assert o.tpot_s == rep["tpot_s"] is not None
        assert o.prompt_len == 8 and o.n_tokens == 3
        assert isinstance(o, RequestOutput)


def test_metrics_id_reuse_starts_a_fresh_lifecycle():
    """Review regression: a reused request id (finished request
    resubmitted) must not inherit the previous run's first-token stamp —
    first-write-wins on_first_token would otherwise fabricate a NEGATIVE
    TTFT (old first token < new submit)."""
    m = ServingMetrics()
    m.on_submit(0, now=0.0)
    m.on_first_token(0, now=0.5)
    m.on_finish(0, n_tokens=3, now=1.0)
    m.on_submit(0, now=10.0)                  # same id, second lifecycle
    rep = m.request_report(0)
    assert rep["ttft_s"] is None              # stale stamps cleared
    assert m.summary()["in_flight"] == 1
    m.on_first_token(0, now=10.5)
    m.on_first_token(0, now=12.0)             # preemption-resume: kept
    m.on_finish(0, n_tokens=2, now=11.0)
    rep = m.request_report(0)
    assert rep["ttft_s"] == pytest.approx(0.5)
    assert rep["n_tokens"] == 2


def test_resubmitted_request_reports_fresh_latency():
    """End-to-end twin of the metrics regression: the second serve of the
    same Request object reports its own (positive) TTFT, not one computed
    against the first run's stamps."""
    mesh = make_host_mesh()
    t = {"now": 0.0}

    def clock():
        t["now"] += 1.0
        return t["now"]

    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8,
                                   clock=clock)
    req = Request(id=0, prompt=np.arange(1, 9, dtype=np.int32),
                  max_new_tokens=3)
    eng.submit(req)
    eng.run_until_drained()
    eng.submit(req)
    eng.run_until_drained()
    first, second = eng.completed
    assert second.token_ids == first.token_ids
    assert second.ttft_s is not None and second.ttft_s > 0
    assert second.tpot_s is not None and second.tpot_s > 0


def test_stream_submits_eagerly_before_iteration():
    """Review regression: stream() must put its requests in flight when
    called, not at first next() — a caller who drains the engine some
    other way would otherwise find their requests were silently never
    submitted."""
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8)
    it = eng.stream([Request(id=i, prompt=np.arange(1, 9, dtype=np.int32),
                             max_new_tokens=2) for i in range(2)])
    assert eng.has_work                       # submitted without iterating
    eng.run_until_drained()                   # drained out of band
    assert len(eng.completed) == 2
    assert list(it) == []                     # iterator finds nothing left


def test_sampling_params_accept_numpy_scalars():
    """Review regression: token ids sliced from prompt arrays are np.int32
    (and temperatures may be np.float32) — validate must accept numpy
    scalars, and an np.int32 stop id must actually terminate the stream."""
    prompt = np.arange(1, 9, dtype=np.int32)
    SamplingParams(temperature=np.float32(0.8), top_k=np.int32(5),
                   top_p=np.float64(0.9), seed=np.int64(3),
                   stop_token_ids=(prompt[-1],)).validate(TINY.vocab)
    with pytest.raises(ValueError, match="outside the vocabulary"):
        SamplingParams(stop_token_ids=(np.int32(TINY.vocab),)) \
            .validate(TINY.vocab)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p="0.9").validate()   # TypeError-proof
    mesh = make_host_mesh()
    want = load_goldens("tiny/base")[0]
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=3)
    out = eng.generate([Request(
        id=0, prompt=prompt.copy(), max_new_tokens=6,
        sampling=SamplingParams(stop_token_ids=(np.int32(want[2]),)))])[0]
    assert out.finish_reason == "stop" and out.token_ids == want[:3]


def test_metrics_aggregates_survive_id_reuse():
    """Review regression: resetting a reused id's lifecycle stamps must not
    deflate engine-lifetime aggregates — completions, token totals and the
    throughput span accumulate across lifecycles."""
    m = ServingMetrics()
    m.on_submit(0, now=0.0)
    m.on_first_token(0, now=0.5)
    m.on_finish(0, n_tokens=3, now=1.0)
    m.on_submit(0, now=10.0)                  # reuse
    m.on_first_token(0, now=10.5)
    m.on_finish(0, n_tokens=2, now=11.0)
    s = m.summary()
    assert s["completed"] == 2                # both lifecycles counted
    assert s["total_tokens"] == 5
    # span covers first submit -> last finish: 5 tokens / 11s
    assert s["tokens_per_sec"] == pytest.approx(5 / 11.0)
    assert s["in_flight"] == 0


def test_generate_validates_whole_batch_before_submitting():
    """Review regression: generate() must vet every request (including
    intra-batch duplicate ids) before putting ANY in flight — a malformed
    entry mid-list used to leave its predecessors running with their
    outputs unreturned."""
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(TINY, _params_for(TINY), mesh, slots=2,
                                   max_len=64, block_size=4, prefill_chunk=8)
    ok = Request(id=0, prompt=np.arange(1, 9, dtype=np.int32),
                 max_new_tokens=2)
    bad = Request(id=1, prompt=np.arange(1, 9, dtype=np.int32),
                  sampling=SamplingParams(top_p=0.0))
    with pytest.raises(ValueError, match="request 1"):
        eng.generate([ok, bad])
    assert not eng.has_work                   # ok was NOT left in flight
    dup = Request(id=0, prompt=np.arange(1, 9, dtype=np.int32),
                  max_new_tokens=2)
    with pytest.raises(ValueError, match="appears twice"):
        eng.generate([ok, dup])
    assert not eng.has_work
    assert len(eng.generate([ok])) == 1       # engine still healthy
