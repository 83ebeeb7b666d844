"""The training driver (``bench/drivers/train.py``) with a short traced
window: a traced run times and profiles at most the traffic's
``trace_seconds`` of the window, so that the profiler's trace of a
four-chip step stays one the chip writes in time; an untraced run is the
training driver's own."""
from __future__ import annotations

import dataclasses

from bench.drivers import train
from bench.harness import Run


def run(r: Run) -> dict:
    if r.trace_dir is not None:
        r = dataclasses.replace(
            r, seconds=min(r.seconds, r.cell.traffic["trace_seconds"]))
    return train.run(r)
