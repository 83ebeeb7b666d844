"""The reduction from a profiler trace to metrics, on a small trace recorded
on a TPU v5e (``fixtures/small.xplane.pb``): five runs of one jitted
program inside ``bench.window``, each followed by a 20 ms sleep inside
``client.wait``."""
from __future__ import annotations

import pytest

from bench import trace_reduce as TR
from conftest import FIXTURES


def test_union_and_subtract():
    u = TR.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert TR.measure(u) == 7
    assert TR.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert TR.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert TR.clip([(0, 4), (6, 8), (9, 12)], 2, 10) == \
        [(2, 4), (6, 8), (9, 10)]


def test_collective_names():
    for name in ("all-reduce.3", "all-gather-start", "reduce-scatter.1",
                 "collective-permute-done"):
        assert TR.COLLECTIVE.search(name)
    for name in ("fusion.12", "convolution.4", "copy.1"):
        assert not TR.COLLECTIVE.search(name)


@pytest.fixture(scope="module")
def small():
    path = FIXTURES / "small.xplane.pb"
    if not path.exists():
        pytest.skip("no recorded trace")
    return TR.reduce(FIXTURES)


def test_recorded_trace(small):
    assert small["devices"] == 1
    assert 0 < small["busy_s"] < small["window_s"]
    prog = [p for n, p in small["programs"].items() if "small_step" in n]
    # the device's clock and the host span's agree to about a millisecond,
    # so the run at the window's opening edge may fall outside it
    assert len(prog) == 1 and round(prog[0]["calls"]) in (4, 5)
    assert all(not n.startswith("%") or " = " not in n
               for n, _ in small["breakdown"]["device_ops"])
    idle = dict(small["breakdown"]["idle_gaps"])
    # five 20 ms sleeps, each inside client.wait
    assert 0.09 < idle.get("client.wait", 0) < 0.2
    assert max(idle, key=idle.get) == "client.wait"
    assert small["breakdown"]["device_ops"]
    assert small["collective_s"] == 0
