"""Serving launcher CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-2.7b --smoke \
        --requests 8 --prompt-len 16 --max-new 12

    # seeded nucleus sampling with stop tokens
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --temperature 0.8 --top-p 0.95 --seed 7 --stop 11,12

Serves through the continuous-batching engine over the unified serving
cache (paged KV / latent block pools + slot-state pools for SSM, cross-attn
and encoder K/V state) with chunked prefill and per-slot positions
(repro/serving/), and prints a metrics line (TTFT/TPOT/occupancy/tokens-
per-sec).  Serves every config in the zoo — zamba2's weight-shared block,
whisper's encoder-decoder and deepseek's MLA included.

--temperature /
--top-k / --top-p    per-request SamplingParams for every submitted request
                     (temperature 0 = exact greedy argmax, the default).
--seed               base RNG seed; request i samples with seed+i.  Token
                     streams are deterministic — identical across reruns
                     and across recompute-preemptions.
--stop               comma-separated token ids: sampling one finishes the
                     request with finish_reason="stop" (the stop token is
                     the last entry of token_ids).
--logprobs           attach per-token logprobs to each RequestOutput.
--share-prefix       cross-request prefix caching (purely paged archs only):
                     prompts share a system prefix of
                     --shared-prefix-len tokens, later requests reuse its
                     cached blocks and start prefill at the matched boundary;
                     the report line gains the prefix-cache hit rate.

Observability:
--trace-out PATH     record a Chrome trace-event JSON of the whole run —
                     one track per engine phase (admission / prefix-match /
                     prefill launch and sync / decode launch / sample
                     host-sync / commit) plus a lifecycle span per request
                     with preemption/resume annotations.
                     Open it in Perfetto (ui.perfetto.dev) or
                     chrome://tracing.
--prom-out PATH      write the final metrics registry as Prometheus text
                     exposition.
--metrics-every S    with --metrics-out: append a windowed-signal JSONL
                     snapshot to <metrics-out>.jsonl every S seconds of
                     engine time (atomic rewrite per snapshot).
--metrics-window S   sliding-window length for the workload signal vector
                     (arrival rate / prompt mix / prefix hit rate / block
                     pressure; default 10s).
"""
from __future__ import annotations

import argparse
import collections
import sys

import jax
import numpy as np

from repro.configs import ARCHS, get_arch, reduce_for_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.serving.engine import init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged-KV block size")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prompt tokens prefilled per engine step")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="physical KV blocks (default: slots*max_len worth)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = disabled)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed; request i uses seed+i")
    ap.add_argument("--stop", default=None,
                    help="comma-separated stop token ids (finish_reason="
                         "'stop' when one is sampled)")
    ap.add_argument("--logprobs", action="store_true",
                    help="attach per-token logprobs to each RequestOutput")
    ap.add_argument("--share-prefix", action="store_true",
                    help="reuse cached KV blocks across requests sharing a "
                         "prompt prefix")
    ap.add_argument("--shared-prefix-len", type=int, default=None,
                    help="with --share-prefix: length of the common system "
                         "prefix prepended to every prompt (default: "
                         "prompt-len, i.e. suffixes of 4 unique tokens)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine's JSON metrics here")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON (Perfetto) of the "
                         "run: per-phase tracks + per-request spans")
    ap.add_argument("--prom-out", default=None,
                    help="write the final metrics as Prometheus text "
                         "exposition")
    ap.add_argument("--metrics-every", type=float, default=None,
                    help="with --metrics-out: append a windowed-signal JSONL "
                         "snapshot to <metrics-out>.jsonl every S seconds of "
                         "engine time")
    ap.add_argument("--metrics-window", type=float, default=10.0,
                    help="sliding-window seconds for the workload signal "
                         "vector (default 10)")
    ap.add_argument("--sanitize", action="store_true",
                    help="attach the paged-cache "
                         "sanitizer (analysis/sanitizer.py) — records "
                         "allocation sites, cross-validates refcounts "
                         "against block tables and the prefix index every "
                         "step, and fails loudly on leaks/double-frees at "
                         "drain; prints an activity report")
    args = ap.parse_args()
    if args.metrics_every is not None and not args.metrics_out:
        ap.error("--metrics-every needs --metrics-out (snapshots go to "
                 "<metrics-out>.jsonl)")

    enable_compile_cache()
    arch = get_arch(args.arch)
    if args.smoke:
        arch = reduce_for_smoke(arch)
    mesh = make_host_mesh()
    params = init_params(arch, mesh, jax.random.PRNGKey(0), slots=args.slots,
                         max_len=args.max_len)
    rng = np.random.default_rng(0)
    if args.share_prefix:
        plen = (args.prompt_len if args.shared_prefix_len is None
                else args.shared_prefix_len)
        shared = rng.integers(1, arch.vocab, size=plen).astype(np.int32)
        prompts = [np.concatenate(
            [shared, rng.integers(1, arch.vocab, size=4).astype(np.int32)])
            for _ in range(args.requests)]
    else:
        prompts = [rng.integers(1, arch.vocab, size=args.prompt_len)
                   .astype(np.int32) for _ in range(args.requests)]

    from repro.serving import (ChromeTracer, ContinuousBatchingEngine,
                               Request, SamplingParams, ServingMetrics,
                               SnapshotWriter, prometheus_text)
    from repro.serving.export import atomic_write_text
    stop_ids = (tuple(int(s) for s in args.stop.split(","))
                if args.stop else ())
    tracer = ChromeTracer() if args.trace_out else None
    snapshot = (SnapshotWriter(args.metrics_out + ".jsonl",
                               every_s=args.metrics_every)
                if args.metrics_every is not None else None)
    sanitizer = None
    if args.sanitize:
        from repro.analysis.sanitizer import CacheSanitizer
        sanitizer = CacheSanitizer()
    engine = ContinuousBatchingEngine(
        arch, params, mesh, slots=args.slots, max_len=args.max_len,
        block_size=args.block_size, num_blocks=args.num_blocks,
        prefill_chunk=args.prefill_chunk, share_prefix=args.share_prefix,
        metrics=ServingMetrics(window_s=args.metrics_window),
        tracer=tracer, snapshot=snapshot, sanitizer=sanitizer)

    def flush_artifacts(out=sys.stdout) -> None:
        """Write every requested artifact through its atomic path.  One
        function for BOTH exits: the success epilogue below and the
        crash path — an engine raise mid-drain must still leave complete,
        loadable trace/metrics/prometheus files (what it captured up to
        the failure), never a stranded half-written snapshot cycle."""
        if args.metrics_out:
            engine.metrics.write(args.metrics_out, engine="continuous",
                                 arch=arch.name)
            print(f"metrics -> {args.metrics_out}", file=out)
        if snapshot is not None:
            snapshot.write(engine.metrics)   # final flush past the cadence
            print(f"snapshots -> {snapshot.path} "
                  f"({snapshot.n_snapshots} lines)", file=out)
        if tracer is not None:
            tracer.write(args.trace_out)
            print(f"trace -> {args.trace_out} (open in ui.perfetto.dev)",
                  file=out)
        if args.prom_out:
            atomic_write_text(args.prom_out, prometheus_text(engine.metrics))
            print(f"prometheus -> {args.prom_out}", file=out)

    try:
        outs = engine.generate([
            Request(id=i, prompt=p, max_new_tokens=args.max_new,
                    sampling=SamplingParams(temperature=args.temperature,
                                            top_k=args.top_k,
                                            top_p=args.top_p,
                                            seed=args.seed + i,
                                            stop_token_ids=stop_ids,
                                            logprobs=args.logprobs))
            for i, p in enumerate(prompts)])
    except Exception as e:
        print(f"engine failed mid-drain: {type(e).__name__}: {e}",
              file=sys.stderr)
        try:
            flush_artifacts(out=sys.stderr)
        except Exception as flush_err:       # the crash exit must survive
            print(f"artifact flush failed: {flush_err}", file=sys.stderr)
        raise SystemExit(1)
    s = engine.metrics.summary()
    reasons = collections.Counter(o.finish_reason for o in outs)
    share = (f", prefix hit rate {s['prefix_hit_rate']:.2f}"
             if args.share_prefix else "")
    mode = ("greedy" if args.temperature == 0 else
            f"T={args.temperature} top_k={args.top_k} top_p={args.top_p} "
            f"seed={args.seed}")

    def ms(x):                       # None-safe: "no data" is not 0.0ms
        return "n/a" if x is None else f"{x * 1e3:.1f}ms"

    print(f"[continuous/{mode}] {s['completed']} requests, "
          f"{s['total_tokens']} tokens, "
          f"{s['decode_steps']} decode steps / {s['prefill_chunks']} prefill "
          f"chunks, ttft mean {ms(s['ttft_mean_s'])} "
          f"p50 {ms(s['ttft_p50_s'])} p95 {ms(s['ttft_p95_s'])} "
          f"p99 {ms(s['ttft_p99_s'])}, tpot p50 {ms(s['tpot_p50_s'])}, "
          f"occupancy {s['slot_occupancy_mean']*100:.0f}%, block util "
          f"{s['block_utilization_mean']:.2f}, "
          f"{s['preemptions']} preemptions, finish reasons "
          f"{dict(reasons)}{share}")
    for o in outs[:3]:
        lp = (f" logprobs[:3]={[round(x, 3) for x in o.logprobs[:3]]}"
              if o.logprobs else "")
        print(f"  req {o.request_id} [{o.finish_reason}] "
              f"{o.token_ids}{lp}")
    flush_artifacts()
    if sanitizer is not None:
        # reaching this line means every per-step and drain check passed
        print(f"sanitizer: clean ({sanitizer.report()})")


if __name__ == "__main__":
    main()
