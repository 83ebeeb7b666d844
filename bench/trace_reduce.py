"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Device planes are ``/device:TPU:<n>``.  Their "XLA Ops" line holds one event
per operation run on the device, the "XLA Modules" line one per program run
(named ``<jit name>(<id>)``).  The host spans that the benchmark opened with
``jax.profiler.TraceAnnotation`` sit on the host plane's thread lines, on the
same clock.

  busy_s        the union of the operations' intervals inside the window,
                the mean over devices
  window_s      the window: the host span ``bench.window``, or the stretch
                from the first to the last device event
  programs      {jit name: {"time_s", "calls"}}: the programs' device time
                in the window, the mean over devices
  collective_s / collective_exposed_s
                time in collectives, and in collectives while no other
                operation runs on that device, the mean over devices
  breakdown     the ten operations that took most device time (summed over
                devices), and the idle time by the innermost host span
                around each gap
"""
from __future__ import annotations

import bisect
import collections
import pathlib
import re

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|send|recv", re.I)
MODULE_ID = re.compile(r"\(\d+\)$")
WINDOW = "bench.window"


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Merged intervals a minus merged intervals b."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _events(line):
    # an operation's event is named by its whole HLO text; keep "%name"
    return [(ev.name.split(" = ", 1)[0], ev.start_ns,
             ev.start_ns + ev.duration_ns) for ev in line.events]


def load(path):
    """(device planes {name: {line name: events}}, host spans)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {ln.name: _events(ln) for ln in plane.lines
                                   if ln.name in ("XLA Ops", "XLA Modules")}
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host += [e for e in _events(ln)
                         if e[0].startswith(("bench.", "engine.", "train.",
                                             "client."))]
    return devices, host


def _around(t, spans, starts, look: int = 64) -> str:
    """The innermost host span around time t: of the spans that cover it,
    the one that started last (the benchmark's spans nest)."""
    i = bisect.bisect_right(starts, t) - 1
    for s, e, name in reversed(spans[max(i - look, 0): i + 1]):
        if s <= t < e:
            return name
    return "outside any span"


def reduce(trace_dir, top: int = 10) -> dict:
    devices, host = load(find_xplane(trace_dir))
    if not devices:
        raise ValueError(f"no TPU device plane in the trace under {trace_dir}")
    win = [(s, e) for n, s, e in host if n == WINDOW]
    if win:
        lo, hi = win[0]
    else:
        every = [ev for d in devices.values() for evs in d.values()
                 for ev in evs]
        lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    n = len(devices)
    busy = coll = exposed = 0.0
    programs = collections.defaultdict(lambda: {"time_s": 0.0, "calls": 0})
    ops_time = collections.Counter()
    idle = collections.Counter()
    spans = sorted((s, e, name) for name, s, e in host if name != WINDOW)
    starts = [s for s, _, _ in spans]
    for lines in devices.values():
        ops = [e for e in lines.get("XLA Ops", []) if e[2] > lo and e[1] < hi]
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy += measure(merged)
        c = union(clip([(s, e) for name, s, e in ops
                        if COLLECTIVE.search(name)], lo, hi))
        other = union(clip([(s, e) for name, s, e in ops
                            if not COLLECTIVE.search(name)], lo, hi))
        coll += measure(c)
        exposed += measure(subtract(c, other))
        for name, s, e in ops:
            ops_time[name] += (min(e, hi) - max(s, lo)) / 1e9
        for name, s, e in lines.get("XLA Modules", []):
            if e > lo and s < hi:
                p = programs[MODULE_ID.sub("", name)]
                p["time_s"] += (min(e, hi) - max(s, lo)) / 1e9 / n
                p["calls"] += 1 / n
        for gs, ge in subtract([(lo, hi)], merged):
            idle[_around((gs + ge) / 2, spans, starts)] += (ge - gs) / 1e9 / n
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9 / n,
        "programs": {k: dict(v) for k, v in programs.items()},
        "collective_s": coll / 1e9 / n,
        "collective_exposed_s": exposed / 1e9 / n,
        "breakdown": {
            "device_ops": [[k, v] for k, v in ops_time.most_common(top)],
            "idle_gaps": [[k, v] for k, v in idle.most_common(top)],
        },
    }
