"""The serving engine's entry point: ``ContinuousBatchingEngine.submit`` and
``step``, driven open loop by one in-process client.

Set-up makes the seed's weights, builds the engine, and serves one request
that spans two prompt chunks, which compiles the paged prefill at
(1, prefill_chunk) and the paged decode at (slots, 1): the only shapes the
window uses.  The window submits each request at its due time and steps the
engine while it has work; each token's time is taken when the engine hands
it to the client.  After the window the client waits, up to the mix's
``drain_s``, for the first token of every request that was due.  Then the
engine is freed and the reference reads a sample of the finished requests.
"""
from __future__ import annotations

import collections
import gc
import math
import time

import numpy as np

from bench.harness import (Capture, Phases, Run, arch_from_config, log,
                           memory_peak_bytes, now, span, traced)

WARM_ID = 1 << 40
SAMPLE_TOKENS = 300          # served tokens the reference reads, at least
SAMPLE_MAX = 8               # requests it reads, at most


class Admissions:
    """A tracer for the engine (the interface of serving/tracing.py's
    ChromeTracer) that keeps only the time each request was first given a
    slot, on the engine's clock, which is the benchmark's.  Set on the
    engine in traced runs alone."""

    def __init__(self):
        self.at: dict[int, float] = {}

    def request_instant(self, rid: int, name: str, t: float, **args) -> None:
        if name == "admitted":
            self.at.setdefault(rid, t)

    def request_begin(self, *a, **kw) -> None:
        pass

    request_end = phase = counter = request_begin


def nearest_rank(xs, q: float) -> float:
    xs = sorted(xs)
    return xs[max(math.ceil(q * len(xs)) - 1, 0)]


class Session:
    """The engine, built once from the seed's weights and warmed up, and
    the client's view of it: each token's arrival time, per request."""

    def __init__(self, cell, seed: int, phases=None):
        import jax

        from bench import weights as W
        from repro.launch.mesh import make_host_mesh
        from repro.models import transformer as T
        from repro.serving import ContinuousBatchingEngine, Request
        from repro.serving.engine import param_shardings, serving_plan

        self.cfg, self.mix = cell.config, cell.traffic
        sv = self.sv = self.cfg["serving"]
        self.arch = arch = arch_from_config(self.cfg)
        mesh = make_host_mesh(cell.chips)
        self.devices = jax.devices()[:cell.chips]
        plan = serving_plan(arch, mesh, slots=sv["slots"],
                            max_len=sv["max_len"])
        self.abstract = jax.eval_shape(
            lambda: T.init_lm(jax.random.PRNGKey(0), arch))
        self.times: dict[int, list] = {}
        if phases:
            phases.mark("plan")
        self.engine = e = ContinuousBatchingEngine(
            arch, W.make_params(self.abstract, seed,
                                param_shardings(plan, mesh)),
            mesh, slots=sv["slots"], max_len=sv["max_len"],
            block_size=sv["block_size"], prefill_chunk=sv["prefill_chunk"],
            num_blocks=sv.get("num_blocks"),
            share_prefix=self.mix["share_prefix"], on_token=self.on_token)
        e._prefill = self.prefill = Capture(e._prefill)
        e._decode = self.decode = Capture(e._decode)
        if phases:
            phases.mark("weights and engine")
        warm = np.arange(1, sv["prefill_chunk"] + 2, dtype=np.int32) \
            % arch.vocab
        e.generate([Request(id=WARM_ID, prompt=warm, max_new_tokens=2)])
        self.times.clear()
        if phases:
            phases.mark("warm-up")

    def on_token(self, rid: int, tok: int) -> None:
        self.times.setdefault(rid, []).append(now())

    def window(self, arrivals, seconds: float, trace_dir=None) -> dict:
        """Serve ``arrivals`` open loop for ``seconds``; what was seen."""
        from repro.serving import Request

        e, times = self.engine, self.times
        times.clear()
        e.tracer = Admissions() if trace_dir is not None else None
        due, failed, steps = {}, set(), 0
        i, n = 0, len(arrivals)
        with traced(trace_dir):
            t0 = now()
            with span("bench.window"):
                while True:
                    t = now()
                    while i < n and t0 + arrivals[i].due_s <= t:
                        a = arrivals[i]
                        due[a.index] = t0 + a.due_s
                        try:
                            with span("engine.submit"):
                                e.submit(Request(
                                    id=a.index, prompt=a.prompt,
                                    max_new_tokens=a.max_new_tokens),
                                    now=due[a.index])
                        except ValueError as err:
                            log(f"request {a.index} refused: {err}")
                            failed.add(a.index)
                        i += 1
                    if t - t0 >= seconds:
                        break
                    if e.has_work:
                        with span("engine.step"):
                            e.step()
                        steps += 1
                    else:
                        nxt = t0 + (arrivals[i].due_s if i < n else seconds)
                        with span("client.wait"):
                            time.sleep(max(0.0, nxt - now()))
            t1 = now()
        return {"t0": t0, "t1": t1, "due": due, "failed": failed,
                "steps": steps, "backlog": e.scheduler.queue_depth,
                "admitted": e.tracer.at if e.tracer is not None else None}

    def drain(self, w: dict, seconds: float, first_only: bool = True) -> int:
        """Step on, up to ``seconds``, until every request that was due has
        its first token (``first_only``) or has finished; -> how many have
        not."""
        e, deadline = self.engine, now() + seconds
        done = {o.request_id for o in e.completed}

        def waiting():
            return [rid for rid in w["due"] if rid not in w["failed"] and (
                rid not in self.times if first_only else rid not in done)]
        left = waiting()
        while left and now() < deadline and e.has_work:
            e.step()
            done = {o.request_id for o in e.completed}
            left = waiting()
        return len(left)

    def latency(self, w: dict, t_end: float) -> tuple[list, list]:
        """(time to first token of each request due, every gap between
        tokens before the window closed); a request with no first token
        counts as waiting until ``t_end``."""
        ttft = [(self.times[rid][0] if rid in self.times else t_end)
                - w["due"][rid] for rid in w["due"]]
        gaps = [b - a for rid, ts in self.times.items() if rid in w["due"]
                for a, b in zip(ts, ts[1:]) if b <= w["t1"]]
        return ttft, gaps

    def finished(self, w: dict) -> list:
        return [(o.request_id, list(o.token_ids))
                for o in self.engine.completed if o.request_id in w["due"]]


def run(r: Run) -> dict:
    from bench import traffic

    phases = Phases(r.t_start)
    phases.mark("start")
    s = Session(r.cell, r.seed, phases)
    arrivals = traffic.requests(s.mix, r.seed, r.seconds, s.arch.vocab)
    setup_s = now() - r.t_start
    log(f"set-up {setup_s:.1f}s ({phases}); {len(arrivals)} requests due in "
        f"{r.seconds}s")
    w = s.window(arrivals, r.seconds, r.trace_dir)
    window = w["t1"] - w["t0"]
    missed = s.drain(w, s.mix["drain_s"]) if s.mix["drain_s"] > 0 else 0
    ttft, gaps = s.latency(w, now())
    out_tokens = sum(1 for ts in s.times.values() for t in ts
                     if w["t0"] <= t <= w["t1"])
    e2e = {"serve_tok_s": out_tokens / window, "setup_s": setup_s,
           "ttft_p90_ms": 1e3 * nearest_rank(ttft, 0.90)}
    if gaps:
        e2e["itl_p99_ms"] = 1e3 * nearest_rank(gaps, 0.99)
    log(f"window {window:.3f}s: {len(w['due'])} due, {w['steps']} "
        f"steps, {out_tokens} tokens, backlog {w['backlog']}; ttft p50 "
        f"{1e3 * nearest_rank(ttft, 0.5):.1f} p90 {e2e['ttft_p90_ms']:.1f} "
        f"ms; gap p50 {1e3 * nearest_rank(gaps, 0.5) if gaps else 0:.1f} "
        f"ms; {missed} without a first token after the drain")
    finished = s.finished(w)
    peak, estimate = memory_peak_bytes(s.devices, [s.prefill, s.decode])
    log(f"gaps over 200 ms by the second of the window they end in: "
        f"{late_gaps(s.times, w)}")
    record = {"window_s": window, "memory_estimate_bytes": estimate}
    if w["admitted"]:
        waits = [w["admitted"][rid] - d for rid, d in w["due"].items()
                 if w["admitted"].get(rid, math.inf) <= w["t1"]]
        if waits:
            record["queue_wait_ms"] = 1e3 * float(np.median(waits))
    cfg, abstract = s.cfg, s.abstract
    del s
    gc.collect()
    checks = reference_checks(cfg, abstract, r.seed, arrivals, finished)
    return {"attempted": len(w["due"]), "failed": len(w["failed"]) + missed,
            "end_to_end": e2e, "record": record, "checks": checks,
            "memory_peak_bytes": peak}


def late_gaps(times, w, over_s: float = 0.2) -> dict:
    """{second of the window: gaps between tokens longer than ``over_s``
    that ended in it}: a stall of the host shows as a cluster."""
    late = collections.Counter(
        int(b - w["t0"]) for rid, ts in times.items() if rid in w["due"]
        for a, b in zip(ts, ts[1:]) if b <= w["t1"] and b - a > over_s)
    return dict(sorted(late.items()))


def sample(finished, seed: int) -> list:
    """The longest finished request, then others drawn from the seed, until
    the sample holds SAMPLE_TOKENS served tokens or SAMPLE_MAX requests."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda j: -len(finished[j][1]))
    rest = list(np.random.default_rng(seed).permutation(order[1:]))
    picked = [order[0]]
    while rest and len(picked) < SAMPLE_MAX and \
            sum(len(finished[j][1]) for j in picked) < SAMPLE_TOKENS:
        picked.append(int(rest.pop()))
    return [finished[j] for j in picked]


def reference_checks(cfg, abstract, seed, arrivals, finished,
                     control=False) -> list:
    """The reference over a sample of the finished requests: each compared
    number beside its limit (``control``: the float8 control's gaps)."""
    from bench import weights as W
    from bench.reference import qwen3 as ref

    t0 = now()
    sv = cfg["serving"]
    by_id = {a.index: a for a in arrivals}
    picked = sample(finished, seed)
    short = sum(1 for rid, toks in picked
                if len(toks) != min(by_id[rid].max_new_tokens,
                                    sv["max_len"] - len(by_id[rid].prompt)))
    w = ref.weights(W.named_leaves(W.make_params(abstract, seed)))
    worst = 0.0 if picked else float("inf")
    for rid, toks in picked:
        seq = np.concatenate([by_id[rid].prompt, np.asarray(toks, np.int32)])
        g = ref.gaps(w, cfg, seq, len(by_id[rid].prompt), sv["max_len"],
                     control=control)
        worst = max(worst, float(np.max(g)) if np.all(np.isfinite(g))
                    else float("inf"))
    log(f"reference {now() - t0:.1f}s over {len(picked)} requests, "
        f"{sum(len(t) for _, t in picked)} served tokens")
    return [{"name": "max_logit_gap", "value": worst,
             "limit": cfg["limits"]["max_logit_gap"]},
            {"name": "short_answers", "value": float(short), "limit": 0.0}]
