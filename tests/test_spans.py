"""Host spans on the profiler's clock (core/profiler.py ``Span``): the
serving engine's and the trainer's phases under ``jax.profiler.start_trace``,
read back from the ``.xplane.pb`` with ``jax.profiler.ProfileData``, and the
compile counter."""
import pathlib

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ArchConfig, Segment, ShapeSpec
from repro.core.profiler import Span, compile_count
from repro.data import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.runtime.trainer import TrainConfig, Trainer
from repro.serving import ContinuousBatchingEngine, Request
from repro.serving.engine import SPAN_PHASES
from serving_fixtures import scenario_requests

TINY = ArchConfig(name="tiny-spans", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                  pattern=(Segment(("attn",), 2),), dtype="float32",
                  param_dtype="float32")
SHAPE = ShapeSpec("smoke", 32, 8, "train")

ENGINE_SPANS = ("engine.admit", "engine.prefill.launch", "engine.prefill.sync",
                "engine.decode.launch", "engine.decode.sync", "engine.commit")
TRAIN_SPANS = ("train.put", "train.dispatch", "train.sync", "train.replan",
               "train.checkpoint")
STEP = "test.step"              # the caller's span around one step


def _profiled(trace_dir: pathlib.Path, body) -> list:
    """Run ``body()`` under the profiler (host spans only, as the benchmark
    traces); -> the host plane's events as (name, start, end, stats)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:CPU"):
            events += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats)) for ln in plane.lines
                       for ev in ln.events]
    return events


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _nested_in_one_step(events, names) -> dict:
    """{step index: that step's spans of ``names`` in start order}; asserts
    each span lies inside exactly one step span."""
    steps = sorted((e for e in events if e[0] == STEP), key=lambda e: e[1])
    by_step: dict = {}
    for ev in events:
        if ev[0] in names:
            owners = [i for i, s in enumerate(steps) if _within(ev, s)]
            assert len(owners) == 1, (ev, owners)
            by_step.setdefault(owners[0], []).append(ev)
    return {i: sorted(evs, key=lambda e: e[1]) for i, evs in by_step.items()}


def _run_engine(trace_dir):
    arch, reqs, slots, max_len = scenario_requests("tiny/base")
    eng = ContinuousBatchingEngine(
        arch, T.init_lm(jax.random.PRNGKey(0), arch), make_host_mesh(),
        slots=slots, max_len=max_len)
    for rid, p, mn in reqs:
        eng.submit(Request(id=rid, prompt=p.copy(), max_new_tokens=mn))

    def drive():
        while eng.has_work:
            with jax.profiler.TraceAnnotation(STEP):
                eng.step()
    return eng, reqs, _profiled(trace_dir, drive)


@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    return _run_engine(tmp_path_factory.mktemp("engine_trace"))


def test_engine_spans_tile_each_step(engine_trace):
    eng, reqs, events = engine_trace
    names = {e[0] for e in events}
    assert set(ENGINE_SPANS) <= names
    assert set(SPAN_PHASES) == set(ENGINE_SPANS)
    steps = _nested_in_one_step(events, ENGINE_SPANS)
    assert len(steps) == eng.metrics.engine_steps
    for spans in steps.values():
        # siblings in order, none overlapping; every step opens with
        # admission and closes with its commit
        assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))
        assert spans[0][0] == "engine.admit"
        assert spans[-1][0] == "engine.commit"


def test_engine_span_arguments(engine_trace):
    eng, reqs, events = engine_trace
    rids = {rid for rid, _, _ in reqs}
    prefill = [e for e in events if e[0].startswith("engine.prefill.")]
    assert prefill and all(e[3]["rid"] in rids for e in prefill)
    # every request's prompt was launched from position 0, and synced once
    launches = [e[3] for e in events if e[0] == "engine.prefill.launch"]
    assert {a["rid"] for a in launches if a["start"] == 0} == rids
    assert all(a["tokens"] > 0 for a in launches)
    syncs = [e[3]["rid"] for e in events if e[0] == "engine.prefill.sync"]
    assert sorted(syncs) == sorted(rids)
    admits = [e[3] for e in events if e[0] == "engine.admit"]
    assert sum(a["admitted"] for a in admits) == len(reqs)
    assert all({"queue_depth", "block_utilization"} <= set(a) for a in admits)
    assert sum(e[3]["finished"] for e in events
               if e[0] == "engine.commit") == len(reqs)
    # the step's tail commit carries the traces and compiles of its step
    assert sum(e[3].get("compiles", 0) for e in events
               if e[0] == "engine.commit") == eng.metrics.compiles.value >= 1
    assert all(e[3]["rows"] > 0 for e in events
               if e[0] == "engine.decode.launch")


def test_step_functions_keep_their_names(engine_trace, train_trace):
    """The benchmark finds the programs by these names."""
    eng, _, events = engine_trace
    assert eng._prefill.__name__ == "paged_prefill_step"
    assert eng._decode.__name__ == "paged_decode_step"
    for span, fn in (("engine.prefill.launch", "paged_prefill_step"),
                     ("engine.decode.launch", "paged_decode_step")):
        calls = [e for e in events if e[0] == f"PjitFunction({fn})"]
        assert calls and all(any(_within(c, s) for s in events
                                 if s[0] == span) for c in calls)
    tr, _, events = train_trace
    assert tr._jitted.__name__ == "train_step"
    calls = [e for e in events if e[0] == "PjitFunction(train_step)"]
    assert calls and all(any(_within(c, s) for s in events
                             if s[0] == "train.dispatch") for c in calls)


@pytest.fixture(scope="module")
def train_trace(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("train_trace")
    tr = Trainer(TINY, SHAPE, make_host_mesh(),
                 TrainConfig(checkpoint_every=2),
                 checkpoint_dir=str(tmp / "ck"))
    state = list(tr.init_state())
    data = SyntheticLM(TINY.vocab, 32, 8)
    hist = []

    def drive():
        for _ in range(3):
            with jax.profiler.TraceAnnotation(STEP):
                state[0], state[1], h = tr.train(*state, data, steps=1)
            hist.extend(h)
    events = _profiled(tmp / "trace", drive)
    tr.ckpt.wait()
    return tr, hist, events


def test_trainer_spans_nest_in_each_step(train_trace):
    _, hist, events = train_trace
    assert set(TRAIN_SPANS) <= {e[0] for e in events}
    steps = _nested_in_one_step(events, TRAIN_SPANS)
    assert len(steps) == 3
    for i, spans in steps.items():
        order = [e[0] for e in spans]
        want = list(TRAIN_SPANS[:4]) + (["train.checkpoint"] if i == 1
                                        else [])
        assert order == want, order
        assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))
        assert spans[0][3]["step"] == i + 1


def test_trainer_reports_compiles_per_step(train_trace):
    _, hist, events = train_trace
    assert [h["compiles"] >= 1 for h in hist] == [True, False, False]
    assert [h["compiles"] for h in hist[1:]] == [0, 0]
    dispatch = sorted((e for e in events if e[0] == "train.dispatch"),
                      key=lambda e: e[1])
    assert [e[3]["compiles"] for e in dispatch] == \
        [h["compiles"] for h in hist]
    assert all(h["step_time_s"] > 0 for h in hist)


def test_compile_count_counts_traces_and_compiles():
    before = compile_count()
    f = jax.jit(lambda x: x * 3 + 1)
    f(1.0)
    first = compile_count() - before
    assert first >= 2                     # a trace and a compile
    f(2.0)
    assert compile_count() - before == first
    f(jax.numpy.ones(3))                  # a new shape: traced anew
    assert compile_count() - before > first


def test_span_stamps_the_callers_clock_and_feeds_its_sink():
    ticks = iter(range(10, 100))
    seen = []
    with Span("unit.span", clock=lambda: float(next(ticks)),
              sink=seen.append, a=1) as sp:
        sp.set(b=2)
    assert seen == [sp]
    assert (sp.t0, sp.t1) == (10.0, 11.0)
    assert sp.args == {"a": 1, "b": 2}
    with Span("unit.bare") as bare:       # no clock: nothing stamped
        pass
    assert bare.t0 is None and bare.t1 is None
