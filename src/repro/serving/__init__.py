"""Continuous-batching serving subsystem — Generation API v2.

The public surface is three typed objects plus the engine:

  ``SamplingParams``  per-request decode controls (temperature / top_k /
                      top_p / seed / stop_token_ids / logprobs), validated
                      at ``submit``; ``temperature=0`` (default) is exact
                      greedy argmax.
  ``Request``         input-only: id, prompt, max_new_tokens, priority,
                      sampling, optional modality frontend.  The engine
                      never mutates it.
  ``RequestOutput``   the result: ``token_ids``, ``finish_reason``
                      ("stop" | "length"), optional per-token ``logprobs``,
                      TTFT/TPOT latency joined from ``ServingMetrics``.
  ``ContinuousBatchingEngine``
                      ``submit()`` + ``step()`` for manual control,
                      ``generate(requests)`` submit-and-drain,
                      ``stream(requests)`` yielding (request_id, token)
                      pairs, and an ``on_token`` callback.

Migrating from v1: results used to leak out by mutating
``Request.out_tokens`` in place and setting ``Request.done``; read
``RequestOutput.token_ids`` / ``finish_reason`` from ``engine.completed``
(or the return of ``generate()``) instead.  ``Request`` no longer carries
``out_tokens`` / ``done`` at all, so a finished Request object may be
resubmitted verbatim.  Greedy decode needs no changes beyond that: the
default ``SamplingParams()`` is temperature-0 argmax, token-for-token
identical to v1.

Layers (bottom up):
  paged_cache.py    block-pool KV cache: refcounted free-list allocator +
                    per-request block tables over the device pools from
                    models/transformer.init_paged_cache, plus cross-request
                    shared-prefix block reuse (content-hash chain index
                    over full blocks, LRU pool of unreferenced-but-cached
                    blocks evicted before OOM).
  cache_manager.py  the unified cache manager: the paged block pools plus
                    slot-indexed state pools (mamba2 conv/SSM state,
                    cross-attention K/V — one row per engine slot + a
                    reserved null row), behind one interface and one
                    device pytree laid out with the ASA plan's
                    paged_cache_specs sharding.
  scheduler.py      admission scheduler: FCFS within priority classes,
                    max-tokens-in-flight budgeting, preemption victim choice.
  sampling.py       SamplingParams + the batched per-slot sampler fused
                    into the jitted paged steps: per-row temperature /
                    top-k / top-p / seed arrays, Gumbel categorical on
                    device, keys derived as fold_in(seed, absolute
                    position) so recompute-preemption regenerates
                    identical tokens (which keeps prefix-cache hash
                    chains re-matchable).
  telemetry.py      metric primitives: counters, gauges, log-bucketed
                    histograms (O(1) record, fixed memory, exact p50/p95/
                    p99 within the bucket growth factor) and sliding
                    windows over caller-supplied engine-clock timestamps.
  tracing.py        ChromeTracer: span-based tracing to Chrome trace-event
                    JSON (load in Perfetto / chrome://tracing) — one track
                    per engine phase plus async per-request lifecycle
                    spans; zero cost when the engine runs without one.
  export.py         exporters: Prometheus text exposition of the whole
                    registry, atomic file writes, and the periodic JSONL
                    snapshot writer that streams the windowed signal
                    vector.
  metrics.py        ServingMetrics — the facade over telemetry.py:
                    per-request TTFT/TPOT percentiles, per-phase duration
                    histograms, windowed workload signals
                    (``window_signals()`` — the adaptive scheduler's
                    input), emitted as JSON; one injectable engine clock
                    stamps every lifecycle point.
  engine.py         the continuous-batching engine: per-slot decode
                    positions, admission into freed slots every step,
                    chunked prefill interleaved with decode; serves every
                    architecture in the zoo — attention-only, MoE, MLA
                    latent attention, pure-SSM, hybrid, cross-attention,
                    zamba2's weight-shared block and whisper's
                    encoder-decoder.  ``share_prefix=True`` (purely paged
                    archs only — slot-state rows are per-request and
                    excluded) reuses cached blocks across requests with a
                    shared prompt prefix and starts prefill at the matched
                    boundary.

  prefix_hash.py    the content-hash chain-key scheme (shared with the
                    cluster router's prefix-affinity index — one function,
                    two consumers, so router keys == cache keys by
                    construction).
  detok.py          the detokenization boundary: token-id -> text pieces
                    plus incremental stop-*string* matching with buffered
                    emission (used by the cluster HTTP/SSE frontend; the
                    engine itself stays token-level).
  cluster/          the multi-process serving cluster: engine replica
                    workers behind an NDJSON wire protocol, the
                    prefix-affinity router with replica health, and the
                    stdlib HTTP/SSE frontend (launched via
                    repro.launch.serve_cluster).  Imported explicitly as
                    ``repro.serving.cluster`` — not re-exported here.

The engine is the only serving API.  Its greedy outputs are pinned in
tests/goldens_serving.json, frozen from the retired wave Server.
"""
from repro.serving.cache_manager import (PAGEABLE_KINDS, SLOT_STATE_KINDS,
                                         UnifiedCacheManager)
from repro.serving.detok import (Detokenizer, StopStringMatcher,
                                 default_detokenizer)
from repro.serving.engine import (ContinuousBatchingEngine, Request,
                                  RequestOutput, init_params)
from repro.serving.prefix_hash import chain_keys
from repro.serving.export import (SnapshotWriter, atomic_write_text,
                                  prometheus_text)
from repro.serving.metrics import ServingMetrics
from repro.serving.paged_cache import BlockAllocator, PagedKVCache
from repro.serving.sampling import GREEDY, SamplingParams
from repro.serving.scheduler import RequestScheduler
from repro.serving.telemetry import (Counter, Gauge, LogHistogram,
                                     SlidingWindow, Telemetry)
from repro.serving.tracing import ChromeTracer, validate_chrome_trace

__all__ = ["ContinuousBatchingEngine", "Request", "RequestOutput",
           "init_params",
           "SamplingParams", "GREEDY", "ServingMetrics", "BlockAllocator",
           "PagedKVCache", "UnifiedCacheManager", "RequestScheduler",
           "PAGEABLE_KINDS", "SLOT_STATE_KINDS",
           "Counter", "Gauge", "LogHistogram", "SlidingWindow", "Telemetry",
           "ChromeTracer", "validate_chrome_trace",
           "SnapshotWriter", "atomic_write_text", "prometheus_text",
           "Detokenizer", "StopStringMatcher", "default_detokenizer",
           "chain_keys"]
