"""Telemetry layer tests: histogram percentiles against numpy,
sliding-window expiry under a synthetic clock, Chrome trace-event schema
validation on a forced-preemption engine run, Prometheus exposition
round-trips, snapshot cadence, atomic writes, and the ServingMetrics
summary()-keys regression (the facade must keep every pre-telemetry key).
"""
import json

import jax
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.serving import (ChromeTracer, ContinuousBatchingEngine, Counter,
                           Gauge, LogHistogram, Request, ServingMetrics,
                           SlidingWindow, SnapshotWriter, Telemetry,
                           atomic_write_text, prometheus_text,
                           validate_chrome_trace)
from repro.serving.export import parse_prometheus_text
from repro.serving.telemetry import quantile
from serving_fixtures import load_goldens, scenario_requests

_PARAMS_CACHE: dict[str, dict] = {}


def _params_for(arch):
    if arch.name not in _PARAMS_CACHE:
        # the goldens' weights: non-partitionable threefry (see
        # tests/test_serving.py::_params_for)
        with jax.threefry_partitionable(False):
            _PARAMS_CACHE[arch.name] = T.init_lm(jax.random.PRNGKey(0),
                                                 arch)
    return _PARAMS_CACHE[arch.name]


# ---------------------------------------------------------------------------
# exact quantiles (the TTFT/TPOT path) vs numpy
# ---------------------------------------------------------------------------

def test_quantile_matches_numpy_linear_interpolation():
    rng = np.random.default_rng(0)
    for n in (1, 2, 3, 10, 97):
        xs = rng.exponential(1.0, size=n).tolist()
        for q in (0.0, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
            assert quantile(xs, q) == pytest.approx(
                float(np.quantile(xs, q)), rel=1e-12), (n, q)


def test_quantile_empty_is_none_not_nan():
    assert quantile([], 0.5) is None


# ---------------------------------------------------------------------------
# counters / gauges
# ---------------------------------------------------------------------------

def test_counter_and_gauge_semantics():
    c = Counter()
    assert c.value == 0
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):           # counters only go up
        c.inc(-1)
    g = Gauge()
    assert g.value is None                    # unset is "no data", not 0
    g.set(2.5)
    assert g.value == 2.5
    g.set(None)                               # explicit reset to "no data"
    assert g.value is None


# ---------------------------------------------------------------------------
# log-bucketed histograms
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_bucket_error_of_numpy():
    """p50/p95/p99 from the log-bucketed histogram agree with exact numpy
    quantiles to within the geometric bucket's relative error."""
    rng = np.random.default_rng(1)
    growth = 1.1
    for xs in (rng.lognormal(-4.0, 1.0, size=5000),
               rng.exponential(0.01, size=5000),
               np.full(100, 0.125)):
        h = LogHistogram(growth=growth)
        for x in xs:
            h.record(float(x))
        for q in (0.5, 0.95, 0.99):
            exact = float(np.quantile(xs, q))
            got = h.percentile(q)
            assert got == pytest.approx(exact, rel=growth - 1 + 0.01), q


def test_histogram_exact_stats_and_bitforbit_mean():
    """count/total/min/max are exact, and the mean is bit-for-bit what the
    old unbounded-list implementation computed (same accumulation order)."""
    xs = [0.3, 0.001, 7.5, 0.3, 2.25e-5, 0.9999]
    h = LogHistogram()
    for x in xs:
        h.record(x)
    assert h.count == len(xs)
    assert h.vmin == min(xs) and h.vmax == max(xs)
    assert h.mean == sum(xs) / len(xs)        # exact equality, not approx
    assert h.total == sum(xs)
    s = h.summary()
    assert s["count"] == len(xs) and s["mean"] == sum(xs) / len(xs)
    assert set(s) == {"count", "mean", "min", "max", "p50", "p95", "p99"}


def test_histogram_empty_and_edge_values():
    h = LogHistogram()
    assert h.count == 0 and h.mean is None and h.percentile(0.5) is None
    h.record(0.0)                             # underflow bucket, exact stats
    h.record(1e9)                             # overflow bucket
    assert h.count == 2 and h.vmin == 0.0 and h.vmax == 1e9
    # percentiles stay clamped to observed values even from the open-ended
    # overflow / underflow buckets
    assert 0.0 <= h.percentile(0.01) <= 1e9
    assert 0.0 <= h.percentile(0.99) <= 1e9


def test_histogram_fixed_memory():
    """The whole point of the refactor: recording a million samples must
    not grow storage (the old *_samples lists grew one entry per step)."""
    h = LogHistogram()
    n_buckets = len(h.counts)
    rng = np.random.default_rng(2)
    for x in rng.exponential(0.05, size=100_000):
        h.record(float(x))
    assert len(h.counts) == n_buckets
    assert h.count == 100_000


# ---------------------------------------------------------------------------
# sliding windows under a synthetic clock
# ---------------------------------------------------------------------------

def test_sliding_window_expiry_synthetic_clock():
    w = SlidingWindow(window_s=10.0)
    for t in range(8):                        # t = 0..7, one value each
        w.record(float(t), float(t))
    assert w.count(7.0) == 8
    assert w.total(7.0) == sum(range(8))
    # advance "now": entries at or before now - 10 fall out
    assert w.count(10.5) == 7                 # t=0 expired (0 <= 0.5)
    assert w.count(16.5) == 1                 # only t=7 left
    assert w.values(16.5) == [7.0]
    assert w.mean(16.5) == 7.0
    assert w.count(100.0) == 0
    assert w.mean(100.0) is None and w.vmax(100.0) is None
    assert w.rate(100.0) == 0.0


def test_sliding_window_rate_and_quantile():
    w = SlidingWindow(window_s=5.0)
    for t in (0.0, 1.0, 2.0, 3.0):
        w.record(t, 10.0 * t)
    assert w.rate(3.0) == pytest.approx(4 / 5.0)
    assert w.quantile(0.5, now=3.0) == pytest.approx(
        float(np.quantile([0.0, 10.0, 20.0, 30.0], 0.5)))


def test_telemetry_registry_snapshot():
    t = Telemetry(window_s=4.0)
    t.counter("hits").inc(3)
    t.gauge("ema").set(0.25)
    t.histogram("lat").record(0.5)
    t.window("arr").record(1.0, 7.0)
    snap = t.snapshot(now=2.0)
    assert snap["counters"]["hits"] == 3
    assert snap["gauges"]["ema"] == 0.25
    assert snap["histograms"]["lat"]["count"] == 1
    assert snap["windows"]["arr"]["count"] == 1
    # re-registering a name returns the SAME primitive (facade + exporters
    # may both ask for it), never a fresh zeroed one
    assert t.counter("hits") is t.counters["hits"]
    assert t.counter("hits").value == 3


# ---------------------------------------------------------------------------
# tracing: schema validation on a forced-preemption engine run
# ---------------------------------------------------------------------------

def _synthetic_clock():
    state = {"t": 0.0}

    def clk():
        state["t"] += 1e-3
        return state["t"]
    return clk


def test_trace_schema_valid_on_forced_preemption_run(tmp_path):
    """Drive the tiny/preempt scenario with an 8-block pool (forces
    recompute-preemption) and a tracer attached: the emitted Chrome trace
    must validate (required keys, monotonic ts, balanced B/E, closed async
    request spans), carry preempt+resume annotations, and the goldens must
    still hold with tracing on."""
    arch, reqs, slots, max_len = scenario_requests("tiny/preempt")
    mesh = make_host_mesh()
    tracer = ChromeTracer()
    eng = ContinuousBatchingEngine(
        arch, _params_for(arch), mesh, slots=slots, max_len=max_len,
        block_size=4, num_blocks=8, prefill_chunk=8,
        clock=_synthetic_clock(), tracer=tracer)
    outs = eng.generate([Request(id=rid, prompt=p.copy(), max_new_tokens=mn)
                         for rid, p, mn in reqs])
    assert eng.metrics.preemptions > 0        # the scenario forces it
    assert {o.request_id: o.token_ids for o in outs} == \
        load_goldens("tiny/preempt")          # tracing changes no tokens

    trace = tracer.write(tmp_path / "trace.json")
    on_disk = json.loads((tmp_path / "trace.json").read_text())
    assert on_disk == trace
    stats = validate_chrome_trace(trace)
    assert stats["n_request_spans"] == len(reqs)
    assert stats["n_phase_spans"] > 0
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"admission", "prefill_launch", "prefill_sync", "decode_launch",
            "sample_sync", "commit", "preempt", "resume", "first_token",
            "admitted"} <= names
    # every phase track got a thread_name metadata record
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "thread_name" for e in meta)
    # the per-step counters rode along
    assert any(e["ph"] == "C" and e["name"] == "queue_depth"
               for e in trace["traceEvents"])
    # phase histograms saw the same phases the tracer did
    for phase in ("decode_launch", "sample_sync", "commit"):
        assert eng.metrics.phase[phase].count == sum(
            1 for e in trace["traceEvents"]
            if e["name"] == phase and e["ph"] == "B")


def test_trace_validator_rejects_malformed_traces():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({})
    base = {"pid": 0, "tid": 1, "ts": 0.0}
    with pytest.raises(ValueError, match="missing"):
        validate_chrome_trace({"traceEvents": [{"ph": "B", "ts": 0.0}]})
    with pytest.raises(ValueError, match="unbalanced"):
        validate_chrome_trace(
            {"traceEvents": [dict(base, name="p", ph="B")]})
    with pytest.raises(ValueError, match="time-sorted"):
        validate_chrome_trace({"traceEvents": [
            dict(base, name="p", ph="B", ts=5.0),
            dict(base, name="p", ph="E", ts=1.0)]})
    with pytest.raises(ValueError, match="no open B"):
        validate_chrome_trace({"traceEvents": [
            dict(base, name="p", ph="E")]})
    # E closing the wrong B
    with pytest.raises(ValueError, match="closes"):
        validate_chrome_trace({"traceEvents": [
            dict(base, name="p", ph="B"),
            dict(base, name="q", ph="E", ts=1.0)]})
    # async end without begin
    with pytest.raises(ValueError, match="no open begin"):
        validate_chrome_trace({"traceEvents": [
            dict(base, name="r", ph="e", cat="request", id=1)]})


def test_tracer_disabled_is_free_on_the_engine():
    """tracer=None must add zero per-step objects: the engine only touches
    the tracer behind `is not None` checks."""
    arch, reqs, slots, max_len = scenario_requests("tiny/base")
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(arch, _params_for(arch), mesh,
                                   slots=slots, max_len=max_len,
                                   clock=_synthetic_clock())
    assert eng.tracer is None
    outs = eng.generate([Request(id=rid, prompt=p.copy(), max_new_tokens=mn)
                         for rid, p, mn in reqs])
    assert {o.request_id: o.token_ids for o in outs} == \
        load_goldens("tiny/base")


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_atomic_write_overwrites_and_leaves_no_temp(tmp_path):
    p = tmp_path / "out.json"
    atomic_write_text(p, "first\n")
    atomic_write_text(p, "second\n")
    assert p.read_text() == "second\n"
    assert [f.name for f in tmp_path.iterdir()] == ["out.json"]  # no *.tmp


def test_metrics_write_is_atomic(tmp_path):
    m = ServingMetrics()
    m.on_submit(0, now=0.0)
    m.on_first_token(0, now=0.1)
    m.on_finish(0, n_tokens=2, now=0.5)
    path = tmp_path / "metrics.json"
    m.write(str(path), engine="test")
    rep = json.loads(path.read_text())
    assert rep["engine"] == "test" and rep["completed"] == 1
    assert [f.name for f in tmp_path.iterdir()] == ["metrics.json"]


def test_prometheus_text_round_trip():
    m = ServingMetrics()
    m.on_submit(0, now=0.0, prompt_len=8)
    m.on_first_token(0, now=0.2)
    m.on_step(queue_depth=3, busy_slots=1, slots=2, block_utilization=0.5,
              now=0.3)
    m.on_phase("decode_launch", 0.01)
    m.on_step_time(0.02)
    m.on_compiles(2)
    m.on_finish(0, n_tokens=4, now=0.5, reason="length")
    text = prometheus_text(m)
    parsed = parse_prometheus_text(text)      # raises on any malformed line
    assert parsed["repro_serving_requests_completed_total"][0][1] == "1.0"
    assert parsed["repro_serving_tokens_generated_total"][0][1] == "4.0"
    # histogram series: cumulative buckets end at +Inf == _count
    buckets = parsed["repro_serving_queue_depth_bucket"]
    assert buckets[-1][0]["le"] == "+Inf" and buckets[-1][1] == "1"
    assert parsed["repro_serving_queue_depth_count"][0][1] == "1"
    cums = [int(v) for lab, v in buckets]
    assert cums == sorted(cums)               # cumulative => nondecreasing
    assert parsed["repro_serving_step_time_s_count"][0][1] == "1"
    assert parsed["repro_serving_compiles_total"][0][1] == "2.0"
    # a fresh registry renders its counter and histograms, still parseable
    empty = prometheus_text(ServingMetrics())
    parsed_empty = parse_prometheus_text(empty)
    assert parsed_empty["repro_serving_compiles_total"][0][1] == "0.0"
    assert parsed_empty["repro_serving_step_time_s_count"][0][1] == "0"


def test_prometheus_labels():
    m = ServingMetrics()
    m.on_step(1, 1, 2, now=0.0)
    text = prometheus_text(m, labels={"arch": "tiny-serve"})
    parsed = parse_prometheus_text(text)
    labels, _ = parsed["repro_serving_engine_steps_total"][0]
    assert labels == {"arch": "tiny-serve"}


def test_snapshot_writer_cadence_and_atomicity(tmp_path):
    m = ServingMetrics()
    path = tmp_path / "snap.jsonl"
    w = SnapshotWriter(path, every_s=1.0)
    assert w.maybe_write(m, 0.0)              # first call always writes
    assert not w.maybe_write(m, 0.5)          # cadence not elapsed
    assert not w.maybe_write(m, 0.99)
    assert w.maybe_write(m, 1.0)
    assert w.maybe_write(m, 5.0)
    assert w.n_snapshots == 3
    lines = path.read_text().splitlines()
    assert len(lines) == 3
    for line in lines:                        # every line parses standalone
        snap = json.loads(line)
        assert "window" in snap and "engine_steps" in snap
    with pytest.raises(ValueError):
        SnapshotWriter(path, every_s=0.0)


# ---------------------------------------------------------------------------
# the engine's step-time and phase histograms and its compile counter
# ---------------------------------------------------------------------------

def test_engine_runs_step_monitor_and_phase_histograms():
    arch, reqs, slots, max_len = scenario_requests("tiny/base")
    mesh = make_host_mesh()
    eng = ContinuousBatchingEngine(arch, _params_for(arch), mesh,
                                   slots=slots, max_len=max_len,
                                   clock=_synthetic_clock())
    eng.generate([Request(id=rid, prompt=p.copy(), max_new_tokens=mn)
                  for rid, p, mn in reqs])
    s = eng.metrics.summary()
    assert s["step_time"]["count"] == s["engine_steps"] > 0
    # a new engine's first steps trace and compile its paged steps
    assert eng.metrics.compiles.value >= 1
    assert "step_time_ema_s" not in s["window"]
    assert s["phases"]["admission"]["count"] == s["engine_steps"]
    assert s["phases"]["prefill_launch"]["count"] == s["prefill_chunks"]
    assert s["phases"]["prefill_sync"]["count"] == len(reqs)
    assert s["phases"]["decode_launch"]["count"] == s["decode_steps"]
    assert s["phases"]["sample_sync"]["count"] == s["decode_steps"]
    # one commit after each prefill chunk and one at each step's tail
    assert s["phases"]["commit"]["count"] == \
        s["engine_steps"] + s["prefill_chunks"]
    # live scheduler/cache references surfaced through the facade
    assert s["scheduler"]["admitted"] >= len(reqs)
    assert s["cache"]["num_blocks"] == eng.cache.cfg.num_blocks
    assert s["cache"]["pool_bytes"] > 0


# ---------------------------------------------------------------------------
# summary() regression: the facade keeps every pre-telemetry key
# ---------------------------------------------------------------------------

PRE_TELEMETRY_KEYS = {
    "requests", "completed", "in_flight", "total_tokens", "tokens_per_sec",
    "ttft_mean_s", "ttft_max_s", "tpot_mean_s", "queue_depth_mean",
    "queue_depth_max", "slot_occupancy_mean", "block_utilization_mean",
    "block_utilization_max", "prefix_hit_rate", "preemptions",
    "engine_steps", "prefill_chunks", "decode_steps",
}

NEW_KEYS = {
    "ttft_p50_s", "ttft_p95_s", "ttft_p99_s",
    "tpot_p50_s", "tpot_p95_s", "tpot_p99_s",
    "finish_reasons", "phases", "step_time", "window",
}


def test_summary_keeps_every_pre_telemetry_key():
    m = ServingMetrics()
    s = m.summary()
    missing = (PRE_TELEMETRY_KEYS | NEW_KEYS) - set(s)
    assert not missing, missing
    # populated run: the old keys still mean what they meant
    m.on_submit(0, now=0.0)
    m.on_first_token(0, now=0.5)
    m.on_step(queue_depth=2, busy_slots=1, slots=2, block_utilization=0.25,
              now=0.6)
    m.on_step(queue_depth=4, busy_slots=2, slots=2, block_utilization=0.75,
              now=0.7)
    m.on_finish(0, n_tokens=3, now=1.5, reason="length")
    s = m.summary()
    assert s["tokens_per_sec"] == pytest.approx(2.0)      # 3 tok / 1.5 s
    assert s["ttft_mean_s"] == pytest.approx(0.5)
    assert s["ttft_p50_s"] == pytest.approx(0.5)
    assert s["queue_depth_mean"] == pytest.approx(3.0)    # exact: (2+4)/2
    assert s["queue_depth_max"] == 4
    assert s["slot_occupancy_mean"] == pytest.approx(0.75)
    assert s["block_utilization_max"] == pytest.approx(0.75)
    assert s["finish_reasons"] == {"length": 1}
    assert json.loads(m.to_json())["completed"] == 1      # stays JSON-able


def test_window_signals_vector_under_synthetic_clock():
    """The adaptive scheduler's signal vector: recent-window rates and
    mixes, deterministic under a synthetic clock, with old entries expiring
    out of every signal."""
    m = ServingMetrics(window_s=10.0)
    m.on_submit(0, now=0.0, prompt_len=100)
    m.on_submit(1, now=1.0, prompt_len=200)
    m.on_prefix_match(50, 100, now=1.5)
    m.on_step(queue_depth=2, busy_slots=2, slots=2, block_utilization=0.5,
              now=2.0)
    m.on_finish(0, n_tokens=20, now=3.0)
    sig = m.window_signals()                  # now defaults to last stamp
    assert sig["t"] == 3.0
    assert sig["arrival_rate_hz"] == pytest.approx(2 / 10.0)
    assert sig["prompt_len_mean"] == pytest.approx(150.0)
    assert sig["prompt_len_max"] == 200.0
    assert sig["prefix_hit_rate"] == pytest.approx(0.5)
    assert sig["block_pressure_mean"] == pytest.approx(0.5)
    assert sig["tokens_per_sec"] == pytest.approx(20 / 10.0)
    # 30 seconds later everything has expired: no data, not zeros
    sig = m.window_signals(now=33.0)
    assert sig["arrival_rate_hz"] == 0.0
    assert sig["prompt_len_mean"] is None
    assert sig["prefix_hit_rate"] is None
    assert sig["block_pressure_mean"] is None
