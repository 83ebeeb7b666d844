"""Continuous-batching engine: per-slot decode positions over a unified
serving cache (paged KV / latent block pools + slot-indexed state pools),
admission into freed slots every step, chunked prefill interleaved with
decode.  This is the ONLY decode path and the only serving API.

Generation API v2: requests and results are two typed objects.

  ``Request``        input-only — id, prompt, ``SamplingParams``, priority,
                     optional frontend.  The engine NEVER mutates it;
                     generation state lives in an internal per-request
                     record, so a finished Request may be resubmitted
                     verbatim.
  ``RequestOutput``  what comes back — token ids, ``finish_reason``
                     ("stop" on a stop-token hit, "length" on the
                     max_new_tokens / max_len budget), optional per-token
                     logprobs, and TTFT/TPOT joined from ServingMetrics.

Entry points: ``submit()`` + ``step()``/``run_until_drained()`` for full
control, ``generate(requests)`` for submit-and-drain, ``stream(requests)``
to iterate (request_id, token) pairs as they are sampled, and an
``on_token`` callback fired for every sampled token.

Sampling (serving/sampling.py) is fused into the jitted paged steps: each
batch row carries its own (temperature, top_k, top_p, seed), so one traced
shape serves mixed per-request parameters — greedy rows (temperature=0
lowers exactly to argmax) ride alongside nucleus-sampled rows, and only a
(B,) token vector returns to the host per step.  Sampling keys derive as
``fold_in(seed, absolute_position)``, which makes a recompute-preempted
request regenerate bit-identical tokens — required for its prefix-cache
blocks to re-match at re-admission.

Every architecture in the zoo is served.  Each batch row carries its own
position vector, block table and slot-state row, so a finished request's
slot (and its cache blocks) are reused on the very next step, and a long
prompt is prefilled ``prefill_chunk`` tokens at a time between decode steps
instead of blocking them.  Per-family cache routing
(serving/cache_manager.py):
  * attention-family KV — paged block pools, incl. zamba2's weight-shared
    block (one pool per application via the repeat-stacked axis) and MLA's
    latent (c_kv, k_rope) rows;
  * mamba2 state — slot-state rows, carried as h0 across prefill chunks;
  * cross-attn / whisper encoder K/V — slot-state rows written once at
    admission (the whisper encoder runs there, never per step).

Engine step = admit -> one prefill chunk -> one decode step:
  1. every free slot pulls from the RequestScheduler (priority/FCFS +
     max-tokens budget, footprints capped at max_len) if its prompt's
     blocks fit the pool; admission resets the slot's state-pool rows
     (make_slot_admit_step).  With ``share_prefix`` (purely paged archs
     only — see serving/cache_manager.py) admission first matches the
     longest cached full-block prefix of the request context: matched
     blocks are refcount-shared, prefill starts at the matched boundary
     (TTFT skips the shared system prompt / few-shot prefix), and full
     blocks this request writes are committed back to the content index
     for later requests;
  2. the oldest prefilling request advances one chunk; finishing the prompt
     samples its first token (TTFT);
  3. all decoding slots advance one token.  A slot needing a new block under
     cache pressure first evicts unreferenced prefix-cache blocks, then
     preempts the request with the largest resident cache footprint
     (recompute-style: refcounts dropped, request requeued with
     prompt+generated as its new prefill — slot-state needs no checkpoint:
     re-admission re-zeroes the row, and a sharing request re-matches its
     own retired blocks).

All request-lifecycle timestamps (submit / first token / finish) come from
one injectable ``clock`` — tests pass a synthetic clock and get coherent
TTFT/TPOT instead of mixing fake submit times with real perf_counter
stamps.

Observability: a step is tiled by host spans from the shared primitive
(core/profiler.py ``Span``), one per phase: ``engine.admit``,
``engine.prefill.launch``, ``engine.prefill.sync`` (the first token's
readback), ``engine.decode.launch``, ``engine.decode.sync`` (the token
readback) and ``engine.commit`` (tokens, finishes, prefix commits and, at
the step's tail, per-step metrics and the step's ``compiles``).  Each is a
``jax.profiler.TraceAnnotation``, so under a profiler trace it sits on the
device trace's clock and the device's idle time can be charged to the
phase the host was in.  The same span, timed by the engine's clock, feeds
the ServingMetrics phase histograms (serving/metrics.py ``SPAN_PHASES``
names them) and, when set, the optional ``tracer`` (serving/tracing.py
ChromeTracer: per-phase tracks plus a lifecycle span per request with
admitted/first-token/preempt/resume annotations).  The ``compiles``
counter counts traces and compiles made inside steps.  With
``tracer=None`` (default) no Chrome-trace work happens; an optional
``snapshot`` (serving/export.py SnapshotWriter) appends a windowed-signal
JSONL line every N seconds of engine time.

Greedy decode is token-for-token identical to the retired wave Server: the
paged attention paths mask exactly the same prefix (layers._paged_sdpa,
mla.mla_paged_attention), the slot-state path runs the same recurrence on
gathered rows, and temperature=0 sampling is a bare argmax inside the
fused sampler.  tests/test_serving.py pins this against golden token
sequences frozen from the retired wave Server, for every arch
family, including under forced preemption and on a multi-host (data=4,
model=2) mesh.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Iterable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.asa import AdaptiveScheduler
from repro.core.profiler import Span, compile_count
from repro.launch.mesh import mesh_shape_of
from repro.models import transformer as T
from repro.runtime import steps as ST
from repro.serving.cache_manager import UnifiedCacheManager, check_servable
from repro.serving.metrics import SPAN_PHASES, ServingMetrics
from repro.serving.paged_cache import PagedCacheConfig, blocks_for
from repro.serving.sampling import GREEDY, SamplingParams, make_sampler
from repro.serving.scheduler import RequestScheduler


@dataclasses.dataclass
class Request:
    """Input-only request description (API v2).

    The engine never mutates a Request: generated tokens, finish reason,
    logprobs and latency come back as a ``RequestOutput`` (via
    ``engine.completed``, ``generate()`` or ``stream()``).  Because no
    state sticks to the object, a finished Request may be resubmitted
    as-is (its id must simply not be in flight).
    """
    id: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 16
    priority: int = 0                # lower = more urgent
    sampling: SamplingParams = GREEDY
    # per-request modality input, consumed ONCE at admission: vision patch
    # embeddings (1, n_img_tokens, d_model) -> cross-attn K/V rows, or audio
    # frame embeddings (1, enc_len, d_model) -> encoder pass -> wdec cross
    # K/V rows (transformer.admit_slot)
    frontend: Optional[np.ndarray] = None


@dataclasses.dataclass
class RequestOutput:
    """Typed generation result (API v2).

    finish_reason  "stop"   — a ``stop_token_ids`` member was sampled (it
                              is the last entry of ``token_ids``);
                   "length" — the max_new_tokens / max_len budget ran out.
    logprobs       per-token log-probabilities under the distribution each
                   token was sampled from; None unless the request's
                   ``SamplingParams.logprobs`` was set.
    ttft_s/tpot_s  joined from ServingMetrics at finish time (None if the
                   engine ran without timestamps for this request).
    """
    request_id: int
    token_ids: list
    finish_reason: str               # "stop" | "length"
    prompt_len: int = 0
    logprobs: Optional[list] = None
    ttft_s: Optional[float] = None
    tpot_s: Optional[float] = None

    @property
    def n_tokens(self) -> int:
        return len(self.token_ids)


@dataclasses.dataclass
class _ReqState:
    """Engine-internal mutable generation state for one in-flight request.

    Quacks like the scheduler's request protocol (id / prompt /
    max_new_tokens / priority / out_tokens / _sched_seq), keeping
    RequestScheduler oblivious to the API split; the public Request stays
    untouched.
    """
    req: Request
    seed: int                        # effective seed (params.seed or req.id)
    stop_ids: frozenset
    out_tokens: list = dataclasses.field(default_factory=list)
    logprobs: Optional[list] = None  # [] iff params.logprobs else None
    _sched_seq: Optional[int] = None   # set by RequestScheduler (FCFS order)
    _charged_footprint: Optional[int] = None   # budget charge at admission

    @property
    def id(self) -> int:
        return self.req.id

    @property
    def prompt(self) -> np.ndarray:
        return self.req.prompt

    @property
    def max_new_tokens(self) -> int:
        return self.req.max_new_tokens

    @property
    def priority(self) -> int:
        return self.req.priority

    @property
    def sampling(self) -> SamplingParams:
        return self.req.sampling

    def context(self) -> np.ndarray:
        """prompt + generated-so-far — what a (re-)prefill must cover."""
        if not self.out_tokens:
            return np.asarray(self.req.prompt, np.int32)
        return np.concatenate([np.asarray(self.req.prompt, np.int32),
                               np.asarray(self.out_tokens, np.int32)])


@dataclasses.dataclass
class _Slot:
    idx: int = 0                     # engine slot index == state-pool row
    req: Optional[_ReqState] = None
    state: str = "idle"              # idle | prefill | decode
    pos: int = 0                     # tokens currently resident in the cache
    prefill_pos: int = 0             # prompt tokens already prefilled

    @property
    def busy(self) -> bool:
        return self.req is not None


def serving_plan(arch: ArchConfig, mesh, *, slots: int, max_len: int,
                 asa: Optional[AdaptiveScheduler] = None):
    """The ASA plan an engine with this geometry shards its weights and
    cache by."""
    sched = asa or AdaptiveScheduler(faithful=False)
    return sched.plan(arch, ShapeSpec("serve", max_len, slots, "decode"),
                      mesh_shape_of(mesh))


def param_shardings(plan, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), plan.param_specs())


def init_params(arch: ArchConfig, mesh, key, *, slots: int, max_len: int,
                asa: Optional[AdaptiveScheduler] = None):
    """``T.init_lm(key, arch)`` created directly with the shardings an
    engine of this geometry serves from, so no device ever holds the whole
    model unplaced."""
    plan = serving_plan(arch, mesh, slots=slots, max_len=max_len, asa=asa)
    return jax.jit(lambda k: T.init_lm(k, arch),
                   out_shardings=param_shardings(plan, mesh))(key)


class ContinuousBatchingEngine:
    def __init__(self, arch: ArchConfig, params, mesh, *,
                 slots: int = 4, max_len: int = 512,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefill_chunk: int = 64,
                 share_prefix: bool = False,
                 scheduler: Optional[RequestScheduler] = None,
                 asa: Optional[AdaptiveScheduler] = None,
                 metrics: Optional[ServingMetrics] = None,
                 clock: Callable[[], float] = time.perf_counter,  # reprolint: disable=clock-injection
                 on_token: Optional[Callable[[int, int], None]] = None,
                 tracer=None, snapshot=None, sanitizer=None):
        check_servable(arch)           # precise error for excluded archs
        self.arch, self.mesh = arch, mesh
        self.max_len, self.prefill_chunk = max_len, prefill_chunk
        self.share_prefix = share_prefix
        self._clock = clock
        # on_token(request_id, token_id): fired for every sampled token,
        # in sampling order — the streaming hook stream() builds on
        self.on_token = on_token
        max_blocks_per_seq = blocks_for(max_len, block_size)
        if num_blocks is None:
            num_blocks = slots * max_blocks_per_seq + 1   # +1: null block
        self.plan = serving_plan(arch, mesh, slots=slots, max_len=max_len,
                                 asa=asa)
        cdtype = jnp.float32 if arch.dtype == "float32" else jnp.bfloat16
        self.cache = UnifiedCacheManager(
            arch, PagedCacheConfig(block_size, num_blocks, max_blocks_per_seq,
                                   slots=slots, share_prefix=share_prefix),
            dtype=cdtype, mesh=mesh, specs=self.plan.paged_cache_specs())
        self.params = jax.device_put(params,
                                     param_shardings(self.plan, mesh))
        sampler = make_sampler(arch.vocab)
        # donation follows ST.STEP_DONATION (the cache carry is donated,
        # params never are) — audited by analysis/tracecheck.py
        self._prefill = ST.jit_step(
            "paged_prefill", ST.make_paged_prefill_step(arch, sampler=sampler))
        self._decode = ST.jit_step(
            "paged_decode", ST.make_paged_decode_step(arch, sampler=sampler))
        self._admit_slot_state = ST.jit_step(
            "slot_admit", ST.make_slot_admit_step(arch)) \
            if self.cache.has_slot_state else None
        self.scheduler = scheduler or RequestScheduler()
        # the engine truncates every request to max_len, so the token budget
        # must charge capped footprints — uncapped, a long-prompt request
        # over-charges and stalls admission forever.  The engine OWNS the
        # cap (unconditional overwrite): it mirrors this engine's
        # truncation, and a stale cap from a previous engine with a
        # different max_len would mis-charge the budget
        self.scheduler.footprint_cap = self.max_len
        self.metrics = metrics or ServingMetrics()
        # observability: ChromeTracer (serving/tracing.py) and
        # SnapshotWriter (serving/export.py) are optional and cost nothing
        # when absent
        self.tracer = tracer
        self.snapshot = snapshot
        # live references: summary()/exporters read the scheduler counters
        # and cache geometry at call time instead of per-step pushes
        self.metrics.scheduler_stats = self.scheduler.stats
        self.metrics.cache_stats = self.cache.stats
        # paged-cache sanitizer (analysis/sanitizer.py): explicit via the
        # kwarg, or opt-in for a whole test run via REPRO_SANITIZE=1.  The
        # import is lazy so production engine construction never touches
        # the analysis package
        if sanitizer is None and os.environ.get("REPRO_SANITIZE"):
            from repro.analysis.sanitizer import CacheSanitizer
            sanitizer = CacheSanitizer()
        self.sanitizer = sanitizer
        if self.sanitizer is not None:
            self.sanitizer.attach(self.cache)
        self.slots = [_Slot(idx=i) for i in range(slots)]
        self.completed: list[RequestOutput] = []
        self._states: dict[int, _ReqState] = {}   # queued or running

    # ------------------------------------------------------------------
    def _validate(self, req: Request) -> None:
        """Every reject-at-submit check, with NO state change — so
        ``generate()`` can vet a whole batch before putting any of it in
        flight."""
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.id} has an empty prompt")
        if req.max_new_tokens < 1:
            # a request that may not generate anything would still burn a
            # slot and a full prefill, and the prefill path unconditionally
            # samples its first token — reject instead of emitting one
            raise ValueError(f"request {req.id}: max_new_tokens must be "
                             f">= 1 (got {req.max_new_tokens})")
        if len(req.prompt) >= self.max_len:
            raise ValueError(f"prompt ({len(req.prompt)}) >= max_len")
        if req.id in self._states:
            # block tables are keyed by request id — a duplicate would share
            # (and corrupt) the live request's table
            raise ValueError(f"request id {req.id} is already in flight")
        try:
            req.sampling.validate(self.arch.vocab)
        except ValueError as e:      # reject-at-submit, like the shape checks
            raise ValueError(f"request {req.id}: {e}") from None
        if blocks_for(self._target_total(req), self.cache.cfg.block_size) \
                > self.cache.cfg.num_blocks - 1:
            raise ValueError(f"request {req.id} can never fit the block pool")
        self.scheduler.check_submittable(req)

    def submit(self, req: Request, now: Optional[float] = None) -> None:
        self._validate(req)
        sp = req.sampling
        st = _ReqState(
            req=req,
            # distinct requests get distinct streams by default, but the
            # effective seed depends only on stable request identity, never
            # on scheduling — preemption re-derives the same keys
            seed=(sp.seed if sp.seed is not None else req.id % (2 ** 32)),
            stop_ids=frozenset(sp.stop_token_ids),
            logprobs=[] if sp.logprobs else None)
        self.scheduler.submit(st)        # may raise (token budget) — only a
        self._states[req.id] = st        # queued request claims its id
        t = self._clock() if now is None else now
        self.metrics.on_submit(req.id, t, prompt_len=len(req.prompt))
        if self.tracer is not None:
            self.tracer.request_begin(req.id, t, prompt_len=len(req.prompt),
                                      max_new_tokens=req.max_new_tokens,
                                      priority=req.priority)

    def _target_total(self, req) -> int:
        # same self-truncation as the wave Server's max_len loop bound
        # (req is a Request or a _ReqState — both carry the two fields)
        return min(len(req.prompt) + req.max_new_tokens, self.max_len)

    # ------------------------------------------------------------------
    def _sampling_rows(self, states: Sequence[Optional[_ReqState]]):
        """Per-row sampler parameter arrays for a batch of (possibly None)
        request states — None rows get greedy params and are discarded by
        the caller."""
        n = len(states)
        temp = np.zeros((n,), np.float32)
        top_k = np.zeros((n,), np.int32)
        top_p = np.ones((n,), np.float32)
        seeds = np.zeros((n,), np.uint32)
        for i, st in enumerate(states):
            if st is None:
                continue
            sp = st.sampling
            temp[i], top_k[i], top_p[i] = sp.temperature, sp.top_k, sp.top_p
            seeds[i] = st.seed
        return (jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p),
                jnp.asarray(seeds))

    def _record_token(self, slot: _Slot, tok: int, logp: float) \
            -> Optional[str]:
        """Append one sampled token to the slot's request and return its
        finish reason, if any ("stop" wins when a stop token lands exactly
        on the length budget — it genuinely terminated the stream)."""
        st = slot.req
        st.out_tokens.append(tok)
        if st.logprobs is not None:
            st.logprobs.append(logp)
        if self.on_token is not None:
            self.on_token(st.id, tok)
        if tok in st.stop_ids:
            return "stop"
        if len(st.req.prompt) + len(st.out_tokens) >= self._target_total(st):
            return "length"
        return None

    def _finish(self, slot: _Slot, reason: str) -> None:
        st = slot.req
        self.cache.release(st.id)
        self.scheduler.on_finish(st)
        t = self._clock()
        self.metrics.on_finish(st.id, len(st.out_tokens), t, reason=reason)
        if self.tracer is not None:
            self.tracer.request_end(st.id, t, finish_reason=reason,
                                    n_tokens=len(st.out_tokens))
        del self._states[st.id]
        rep = self.metrics.request_report(st.id)
        self.completed.append(RequestOutput(
            request_id=st.id, token_ids=list(st.out_tokens),
            finish_reason=reason, prompt_len=len(st.req.prompt),
            logprobs=None if st.logprobs is None else list(st.logprobs),
            ttft_s=rep["ttft_s"], tpot_s=rep["tpot_s"]))
        slot.req, slot.state, slot.pos, slot.prefill_pos = None, "idle", 0, 0

    def _preempt(self, slot: _Slot) -> None:
        st = slot.req
        self.cache.release(st.id)
        self.scheduler.preempt(st)
        self.metrics.on_preempt(st.id)
        if self.tracer is not None:
            self.tracer.request_instant(st.id, "preempt", self._clock(),
                                        resident_tokens=slot.pos,
                                        n_generated=len(st.out_tokens))
        slot.req, slot.state, slot.pos, slot.prefill_pos = None, "idle", 0, 0

    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Abort a queued or running request; True iff ``rid`` was in
        flight.  The request finishes with ``finish_reason=reason`` and
        whatever tokens it had produced — the cluster frontend uses this
        when a stop *string* matches at the detokenized boundary (the
        engine stays token-level; see serving/detok.py) and when a client
        disconnects mid-stream.  Running requests release their cache
        blocks and budget charge through the normal ``_finish`` path;
        queued requests hold neither (budget is charged at admission), so
        cancellation there is queue surgery plus the same lifecycle
        bookkeeping — either way metrics/trace/completed stay consistent
        and the drain sanitizer sees a clean engine."""
        st = self._states.get(rid)
        if st is None:
            return False
        for slot in self.slots:
            if slot.busy and slot.req is st:
                self._finish(slot, reason)
                return True
        removed = self.scheduler.remove(st)
        if not removed:
            raise RuntimeError(f"request {rid} tracked but neither running "
                               f"nor queued — lifecycle invariant broken")
        del self._states[rid]
        t = self._clock()
        self.metrics.on_finish(rid, len(st.out_tokens), t, reason=reason)
        if self.tracer is not None:
            self.tracer.request_end(rid, t, finish_reason=reason,
                                    n_tokens=len(st.out_tokens))
        rep = self.metrics.request_report(rid)
        self.completed.append(RequestOutput(
            request_id=rid, token_ids=list(st.out_tokens),
            finish_reason=reason, prompt_len=len(st.req.prompt),
            logprobs=None if st.logprobs is None else list(st.logprobs),
            ttft_s=rep["ttft_s"], tpot_s=rep["tpot_s"]))
        return True

    def outstanding_tokens(self) -> int:
        """Worst-case tokens still to be generated across every queued and
        running request — the load estimate a cluster router balances on
        (exported per worker through the stats protocol)."""
        return sum(
            max(self._target_total(st) - len(st.req.prompt)
                - len(st.out_tokens), 0)
            for st in self._states.values())

    # -- phase 1: admission --------------------------------------------
    def _admit(self) -> int:
        admitted = 0
        for slot in self.slots:
            if slot.busy:
                continue
            head = self.scheduler.peek()
            if head is None:
                break
            ctx = head.context()
            if not self.cache.can_fit_request(ctx):
                if not any(s.busy for s in self.slots):
                    raise RuntimeError(
                        f"request {head.id} cannot fit an empty pool")
                break                      # wait for running requests to free
            st = self.scheduler.next_admission()
            if st is None:                 # token budget exhausted
                break
            # longest cached full-block prefix: refcounts bump, the table
            # starts populated, and prefill starts at the matched boundary
            # (no-op with share_prefix off)
            if self.share_prefix:
                tp0 = self._clock()
                n_cached = self.cache.assign_prefix(st.id, ctx)
                tp1 = self._clock()
                self.metrics.on_phase("prefix_match", tp1 - tp0)
                if self.tracer is not None:
                    self.tracer.phase("prefix_match", tp0, tp1,
                                      request=st.id,
                                      matched_tokens=n_cached)
            else:
                n_cached = self.cache.assign_prefix(st.id, ctx)
            ok = self.cache.reserve(st.id, len(ctx))
            if not ok:
                raise RuntimeError(
                    f"request {st.id}: can_fit_request passed but reserve "
                    f"failed — admission check out of sync with allocator")
            slot.req, slot.state = st, "prefill"
            slot.pos, slot.prefill_pos = n_cached, n_cached
            admitted += 1
            if self.share_prefix:
                self.metrics.on_prefix_match(n_cached, len(ctx),
                                             now=self._clock())
            if self.tracer is not None:
                t = self._clock()
                if st.out_tokens:      # re-admission after preemption
                    self.tracer.request_instant(st.id, "resume", t,
                                                n_generated=len(st.out_tokens))
                self.tracer.request_instant(st.id, "admitted", t,
                                            slot=slot.idx,
                                            context_len=len(ctx),
                                            prefix_cached_tokens=n_cached)
            if self._admit_slot_state is not None:
                # reset this slot's state-pool rows (zero mamba2 state;
                # cross K/V from the request's frontend, computed once)
                args = (self.params, self.cache.pools,
                        jnp.asarray(slot.idx, jnp.int32))
                if st.req.frontend is not None:
                    args += (jnp.asarray(st.req.frontend),)
                self.cache.pools = self._admit_slot_state(*args)
        return admitted

    # -- phase 2: one chunk of prefill ---------------------------------
    def _prefill_chunk(self) -> bool:
        # oldest request first (scheduler seq), not lowest slot index — a
        # newer request admitted into a freed lower slot must not starve an
        # older mid-prefill request's TTFT
        prefilling = [s for s in self.slots if s.state == "prefill"]
        if not prefilling:
            return False
        slot = min(prefilling, key=lambda s: s.req._sched_seq)
        st, start = slot.req, slot.prefill_pos
        with self._span("engine.prefill.launch", rid=st.id,
                        start=start) as sp:
            ctx = st.context()
            chunk = ctx[start: start + self.prefill_chunk]
            n_new = len(chunk)
            sp.set(tokens=n_new)
            if n_new < self.prefill_chunk:  # pad: the step traces one shape
                chunk = np.concatenate(
                    [chunk, np.zeros(self.prefill_chunk - n_new, np.int32)])
            table = self.cache.table_array([st.id])
            tok, logp, self.cache.pools = self._prefill(
                self.params, self.cache.pools, jnp.asarray(chunk[None, :]),
                jnp.asarray([start], jnp.int32), jnp.asarray(table),
                jnp.asarray([n_new], jnp.int32),
                jnp.asarray([slot.idx], jnp.int32),
                *self._sampling_rows([st]))
            slot.prefill_pos += n_new
            slot.pos = slot.prefill_pos
        # the fused sampler produced this chunk's next token at absolute
        # position len(ctx) — only the final chunk's draw is real
        last = slot.prefill_pos == len(ctx)
        if last:
            with self._span("engine.prefill.sync", rid=st.id):
                tok0, logp0 = int(tok[0]), float(logp[0])
        with self._span("engine.commit") as sp:
            self.cache.commit_prefix(st.id, ctx, slot.prefill_pos)
            self.metrics.prefill_chunks += 1
            if last:
                t = self._clock()
                self.metrics.on_first_token(st.id, t)
                if self.tracer is not None:
                    self.tracer.request_instant(st.id, "first_token", t)
                reason = self._record_token(slot, tok0, logp0)
                if reason is not None:
                    self._finish(slot, reason)
                else:
                    slot.state = "decode"
                sp.set(finished=int(reason is not None))
        return True

    # -- phase 3: one decode step for every decoding slot --------------
    def _decode_step(self):
        """Launch one decode step for every decoding slot and read its
        tokens back; -> (tokens, logprobs), or None when no slot decoded.
        The tokens are recorded by ``_commit_decode``."""
        if not any(s.state == "decode" for s in self.slots):
            return None
        with self._span("engine.decode.launch") as sp:
            decoding = [s for s in self.slots if s.state == "decode"]
            # grow block tables; preempt the longest-running request on
            # pressure
            for slot in list(decoding):
                if slot.req is None:   # already preempted as an earlier victim
                    continue
                while not self.cache.reserve(slot.req.id, slot.pos + 1):
                    victims = [s.req for s in self.slots if s.busy]
                    victim = self.scheduler.pick_preemption_victim(victims)
                    vslot = next(s for s in self.slots if s.req is victim)
                    self._preempt(vslot)
                    if vslot in decoding:
                        decoding.remove(vslot)
                    if slot.req is None:       # we preempted ourselves
                        break
            decoding = [s for s in decoding if s.req is not None]
            sp.set(rows=len(decoding))
            if not decoding:
                return None
            B = len(self.slots)
            last = np.zeros((B, 1), np.int32)
            pos = np.zeros((B,), np.int32)
            rids: list[Optional[int]] = [None] * B
            for i, s in enumerate(self.slots):
                if s.state == "decode":
                    last[i, 0] = s.req.out_tokens[-1]
                    pos[i] = s.pos
                    rids[i] = s.req.id
            table = self.cache.table_array(rids)
            # idle/prefilling rows scatter their slot-state into the null
            # row; active rows use s.idx (NOT list position —
            # admission/prefill reset/advance the pool row at idx, and the
            # two may diverge)
            sids = self.cache.slot_ids_array(
                [s.idx if s.state == "decode" else None for s in self.slots])
            tok, logp, self.cache.pools = self._decode(
                self.params, self.cache.pools, jnp.asarray(last),
                jnp.asarray(pos), jnp.asarray(table), jnp.asarray(sids),
                *self._sampling_rows(
                    [s.req if s.state == "decode" else None
                     for s in self.slots]))
        # the (B,) token/logprob transfer is where the host blocks on the
        # device
        with self._span("engine.decode.sync", rows=len(decoding)):
            return np.asarray(tok), np.asarray(logp)

    def _commit_decode(self, nxt: np.ndarray, lps: np.ndarray) -> None:
        self.metrics.decode_steps += 1
        for i, s in enumerate(self.slots):
            if s.state != "decode":
                continue
            s.pos += 1
            reason = self._record_token(s, int(nxt[i]), float(lps[i]))
            if self.share_prefix and s.pos % self.cache.cfg.block_size == 0:
                # a block just filled: generated tokens extend the hash
                # chain too, so a preempted request re-matches its own
                # retired blocks at re-admission.  Gated on the boundary —
                # rebuilding context() every token would be O(n^2) per
                # request in the decode hot loop.  Committed even when the
                # request finishes right here: the block retires to the LRU
                # index and stays matchable
                self.cache.commit_prefix(s.req.id, s.req.context(), s.pos)
            if reason is not None:
                self._finish(s, reason)

    # ------------------------------------------------------------------
    def _span(self, name: str, **args) -> Span:
        return Span(name, self._clock, self._on_span, **args)

    def _on_span(self, sp: Span) -> None:
        phase = SPAN_PHASES[sp.name]
        self.metrics.on_phase(phase, sp.t1 - sp.t0)
        if self.tracer is not None:
            self.tracer.phase(phase, sp.t0, sp.t1, **sp.args)

    def step(self) -> None:
        """admit -> one prefill chunk -> one decode step, then commit."""
        t0 = self._clock()
        compiles0 = compile_count()
        with self._span("engine.admit") as sp:
            admitted = self._admit()
            sp.set(admitted=admitted, queue_depth=self.scheduler.queue_depth,
                   block_utilization=self.cache.utilization)
        self._prefill_chunk()
        sampled = self._decode_step()
        with self._span("engine.commit") as sp:
            finished = len(self.completed)
            if sampled is not None:
                self._commit_decode(*sampled)
            t = self._clock()
            util = self.cache.utilization
            if self.tracer is not None:
                self.tracer.counter("queue_depth", t,
                                    self.scheduler.queue_depth)
                self.tracer.counter("block_utilization", t, util)
            self.metrics.on_step(self.scheduler.queue_depth,
                                 sum(s.busy for s in self.slots),
                                 len(self.slots), block_utilization=util,
                                 now=t)
            self.metrics.on_step_time(t - t0)
            compiles = compile_count() - compiles0
            self.metrics.on_compiles(compiles)
            if self.snapshot is not None:
                self.snapshot.maybe_write(self.metrics, t)
            if self.sanitizer is not None:
                self.sanitizer.check_engine_step(self)
            sp.set(finished=len(self.completed) - finished,
                   compiles=compiles)

    @property
    def has_work(self) -> bool:
        return self.scheduler.queue_depth > 0 or any(s.busy for s in self.slots)

    def _progress_marker(self) -> tuple:
        return (self.metrics.prefill_chunks, self.metrics.decode_steps,
                self.metrics.preemptions, len(self.completed),
                self.scheduler.queue_depth,
                sum(s.busy for s in self.slots))

    def run_until_drained(self, *, max_idle_steps: int = 1000) -> float:
        """Step until no queued or running work remains.  Raises after
        ``max_idle_steps`` consecutive steps that neither prefill, decode,
        preempt, finish, admit nor drain anything — a stuck engine (e.g. a
        token budget that can never re-admit) must fail loudly instead of
        spinning forever."""
        t0 = self._clock()
        idle, marker = 0, self._progress_marker()
        while self.has_work:
            self.step()
            now = self._progress_marker()
            idle = idle + 1 if now == marker else 0
            marker = now
            if idle >= max_idle_steps:
                raise RuntimeError(
                    f"engine made no progress for {idle} consecutive steps "
                    f"({self.scheduler.queue_depth} queued, "
                    f"{sum(s.busy for s in self.slots)} busy slots) — "
                    f"admission is wedged")
        if self.sanitizer is not None:
            self.sanitizer.check_drained(self)
        return self._clock() - t0

    # -- v2 entry points ------------------------------------------------
    def generate(self, requests: Iterable[Request]) -> list[RequestOutput]:
        """Submit every request, run the engine until drained, and return
        their ``RequestOutput``s in the order given (independent of finish
        order).  Outputs also accumulate on ``self.completed``.  The whole
        batch is validated before ANY request is submitted, so a malformed
        entry raises with nothing newly in flight."""
        reqs = list(requests)
        seen: set[int] = set()
        for r in reqs:
            self._validate(r)
            if r.id in seen:
                raise ValueError(f"request id {r.id} appears twice in the "
                                 f"batch")
            seen.add(r.id)
        for r in reqs:
            self.submit(r)
        self.run_until_drained()
        by_id = {o.request_id: o for o in self.completed}  # latest id wins
        return [by_id[r.id] for r in reqs]

    def stream(self, requests: Iterable[Request]) \
            -> Iterator[tuple[int, int]]:
        """Submit every request (eagerly, before returning — the requests
        are in flight even if the iterator is never advanced) and step the
        engine as the returned iterator is consumed, yielding
        ``(request_id, token_id)`` pairs in sampling order as they are
        produced — including tokens of requests that were already in
        flight.  A caller-installed ``on_token`` keeps firing too.
        Abandoning the iterator mid-stream leaves the engine with work in
        flight (resume with ``step()``/``run_until_drained()``)."""
        for r in requests:
            self.submit(r)

        def _drive() -> Iterator[tuple[int, int]]:
            buf: list[tuple[int, int]] = []
            prev = self.on_token

            def tap(rid: int, tok: int) -> None:
                if prev is not None:
                    prev(rid, tok)
                buf.append((rid, tok))

            self.on_token = tap
            try:
                while self.has_work:
                    self.step()
                    while buf:
                        yield buf.pop(0)
            finally:
                self.on_token = prev

        return _drive()
