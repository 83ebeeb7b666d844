"""The knee of a serving mix: the highest offered rate at which 90% of the
requests meet the mix's limits on time to first token (``ttft_limit_ms``)
and on every gap between tokens (``itl_limit_ms``), with no backlog left
growing: at most ``MAX_BACKLOG`` requests still wait for a slot when the
window closes.  One engine serves a window at each rate in turn and is
drained between them.

    python bench/knee.py --workload <cell> --rates 0.6,0.8,1.0 [--seconds 51]

Each rate prints one JSON line: the share of requests that met both limits,
the backlog at the close, whether the rate passes, and the latency
quantiles.  A cell's rate is fixed from this once (its ``rate_per_s``); the
benchmark never searches.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

MAX_BACKLOG = 2


def main(argv=None) -> int:
    from bench import traffic
    from bench.drivers.serve import Session, nearest_rank
    from bench.harness import load_cell, now
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    enable_compile_cache()
    cell = load_cell(a.workload)
    s = Session(cell, a.seed)
    mix = s.mix
    for rate in (float(x) for x in a.rates.split(",")):
        arrivals = traffic.requests(mix, a.seed, a.seconds, s.arch.vocab,
                                    rate_per_s=rate)
        w = s.window(arrivals, a.seconds)
        left = s.drain(w, 240.0, first_only=False)
        ttft, gaps = s.latency(w, now())
        met = 0
        for rid, d in w["due"].items():
            ts = s.times.get(rid, [])
            worst_gap = max((b - x for x, b in zip(ts, ts[1:])), default=0.0)
            met += bool(ts) and ts[0] - d <= mix["ttft_limit_ms"] / 1e3 \
                and worst_gap <= mix["itl_limit_ms"] / 1e3
        print(json.dumps({
            "rate_per_s": rate, "due": len(w["due"]),
            "met_share": met / len(w["due"]),
            "backlog_at_close": w["backlog"], "unfinished": left,
            "passes": met >= 0.9 * len(w["due"])
            and w["backlog"] <= MAX_BACKLOG,
            "ttft_p50_ms": 1e3 * nearest_rank(ttft, 0.5),
            "ttft_p90_ms": 1e3 * nearest_rank(ttft, 0.9),
            "gap_p50_ms": 1e3 * nearest_rank(gaps, 0.5),
            "gap_p99_ms": 1e3 * nearest_rank(gaps, 0.99),
            "tok_s": sum(1 for ts in s.times.values() for t in ts
                         if w["t0"] <= t <= w["t1"]) / (w["t1"] - w["t0"])}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
