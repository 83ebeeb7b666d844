"""The per-layer readers of the program's host spans, on hand-made results
of ``trace_reduce.reduce``: ``host_idle_ms.train`` and
``host_idle_share.chat``."""
from __future__ import annotations

import pytest

from bench.harness import load_module
from conftest import BENCH


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py", f"test_{name}").read


TRAIN = _reader("host_idle_ms.train")
CHAT = _reader("host_idle_share.chat")


def _trace(idle: dict, programs=None, window_s=50.0) -> dict:
    return {"devices": 1, "window_s": window_s, "busy_s": window_s - 1.0,
            "programs": programs if programs is not None else {},
            "collective_s": 0.0, "collective_exposed_s": 0.0,
            "breakdown": {"device_ops": [["%while.1", 40.0]],
                          "idle_gaps": sorted(([k, v] for k, v in
                                               idle.items()),
                                              key=lambda kv: -kv[1])}}


STEP = {"jit_train_step": {"time_s": 49.0, "calls": 100.0}}


def test_train_sums_its_spans_over_the_step_calls():
    tr = _trace({"train.put": 0.1, "train.dispatch": 0.5,
                 "train.replan": 0.02, "train.checkpoint": 0.08,
                 "train.sync": 0.3, "train.call": 0.01,
                 "outside any span": 0.001}, STEP)
    # (0.1 + 0.5 + 0.02 + 0.08 + 0.3) s over 100 calls; the benchmark's
    # own call span and idle outside every span are left out
    assert TRAIN({}, tr, {}) == pytest.approx(10.0)


def test_chat_sums_its_spans_over_the_window():
    tr = _trace({"engine.admit": 0.1, "engine.prefill.launch": 0.4,
                 "engine.decode.launch": 0.7, "engine.commit": 0.3,
                 "engine.prefill.sync": 0.2, "engine.decode.sync": 0.9,
                 "engine.step": 0.05, "engine.submit": 0.01,
                 "client.wait": 2.0, "outside any span": 0.001},
                {"jit_paged_decode_step": {"time_s": 40.0, "calls": 300.0}})
    assert CHAT({}, tr, {}) == pytest.approx(100 * 2.6 / 50.0)


@pytest.mark.parametrize("reader, idle, want", [
    (TRAIN, {"train.dispatch": 0.4, "train.sync": 0.2}, 6.0),
    (TRAIN, {"train.replan": 0.2, "train.call": 0.3}, 2.0),
    (CHAT, {"engine.commit": 0.5, "engine.decode.sync": 0.2}, 1.4),
    (CHAT, {"engine.decode.sync": 0.2, "engine.step": 0.1}, 0.4),
])
def test_absent_spans_read_zero(reader, idle, want):
    assert reader({}, _trace(idle, STEP), {}) == pytest.approx(want)


@pytest.mark.parametrize("reader, a, b", [
    (TRAIN, "train.sync", "train.put"),
    (TRAIN, "train.sync", "train.dispatch"),
    (CHAT, "engine.decode.sync", "engine.decode.launch"),
    (CHAT, "engine.prefill.sync", "engine.commit"),
])
def test_a_gap_charged_to_either_side_reads_the_same(reader, a, b):
    """A gap from a readback into the next launch goes whole to the span
    that holds its midpoint; the reading must not follow that choice."""
    other = {"engine.step": 0.05, "train.call": 0.05}
    one = reader({}, _trace({a: 1.2, b: 0.1, **other}, STEP), {})
    two = reader({}, _trace({a: 0.1, b: 1.2, **other}, STEP), {})
    assert one == pytest.approx(two) and one > 0


@pytest.mark.parametrize("reader, idle", [
    (TRAIN, {"train.call": 0.9, "bench.window": 0.01}),
    (CHAT, {"engine.step": 2.4, "engine.submit": 0.01, "client.wait": 1.0,
            "outside any span": 0.004}),
])
def test_only_the_benchmarks_spans_read_none(reader, idle):
    """A program without spans of its own (the parent of the spans)."""
    assert reader({}, _trace(idle, STEP), {}) is None


def test_train_without_a_train_step_program_reads_none():
    idle = {"train.dispatch": 0.4}
    assert TRAIN({}, _trace(idle, {}), {}) is None
    other = {"jit_paged_decode_step": {"time_s": 1.0, "calls": 5.0}}
    assert TRAIN({}, _trace(idle, other), {}) is None


@pytest.mark.parametrize("reader", [TRAIN, CHAT])
def test_no_trace_reads_none(reader):
    assert reader({"window_s": 51.0}, None, {}) is None
