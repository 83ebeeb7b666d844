"""Bring-up check on a TPU: the serving engine and the trainer, each through
its normal constructor, at published widths, plus the Pallas kernels
compiled for the chip.

    python chip_smoke.py [--seed N]      # one chip: serve, train, kernels
    python chip_smoke.py --chips 4       # one host of four chips: sharded
                                         # training against one device

Phases (one chip):

  serve    qwen3-8b at published widths, bf16, 8 of 36 layers, behind
           ContinuousBatchingEngine: 8 requests of 512 prompt tokens and
           32 new tokens (6 greedy, 2 seeded sampled, all with logprobs).
           Every request must finish; for two greedy requests each
           generated token's logprob must match a plain full-context
           forward of the same weights.
  train    mamba2-780m at published widths, bf16, 16 of 48 layers, behind
           Trainer: 5 steps of batch 8 x 1024 tokens; every loss finite.
  kernels  flash_attention and ssd_scan compiled for the chip (never
           interpreted) at the serve and train geometries, against
           kernels/ref.py.

With ``--chips 4`` only the sharded check runs: the train phase's model
for 5 steps on a four-chip mesh under the plan AdaptiveScheduler picks,
then the same steps on one device, in this one process.

Weights and traffic come from ``--seed``.  Any failed check raises, and
the exit code is then not 0.  The last line of standard output is one
JSON object naming the device; it is printed only when every phase
passed, and never when jax finds no TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SERVE_LAYERS, TRAIN_LAYERS = 8, 16
SLOTS, MAX_LEN, BLOCK, CHUNK = 8, 1024, 16, 256
PROMPT, NEW, SAMPLED = 512, 32, 2
SEQ, BATCH, STEPS = 1024, 8, 5

# Engine logprobs against the dense forward, in nats.  Both paths run the
# same bf16 weights in bf16; they differ only in how attention and matmul
# partial sums are ordered (paged chunks and single-token decode against
# one causal pass), which moves bf16-rounded activations by an ulp here
# and there.  At this init the logits have a spread of ~0.9, so that
# noise is a few hundredths of a nat; a token read at the wrong position
# or from the wrong cache block misses by nats.
LOGPROB_TOL = 0.1
# Kernels against their oracle (run at highest matmul precision), as
# max |out - ref| / max |ref|: bf16 in and out for flash attention, and
# the MXU may round f32 operands to bf16 (2^-8 relative) in either kernel.
KERNEL_TOL = 2e-2
# Sharded against one-device losses, relative: the paper's own bound on
# how much the parallelism strategy may move model quality (+-0.5%).
SHARDED_LOSS_RTOL = 5e-3


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(phase: str, ok: bool, msg: str) -> None:
    log(phase, f"{'PASS' if ok else 'FAIL'} {msg}")
    if not ok:
        raise SystemExit(f"{phase}: check failed: {msg}")


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import cut_depth, get_arch
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.serving import (ContinuousBatchingEngine, Request,
                               SamplingParams, ServingMetrics, init_params)

    arch = cut_depth(get_arch("qwen3-8b"), SERVE_LAYERS)
    mesh = make_host_mesh()
    t0 = time.perf_counter()
    params = init_params(arch, mesh, jax.random.PRNGKey(seed), slots=SLOTS,
                         max_len=MAX_LEN)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    engine = ContinuousBatchingEngine(
        arch, params, mesh, slots=SLOTS, max_len=MAX_LEN, block_size=BLOCK,
        prefill_chunk=CHUNK, metrics=ServingMetrics(window_s=10.0))
    jax.block_until_ready(engine.params)
    log("serve", f"{arch.name} d_model {arch.d_model} heads "
        f"{arch.n_heads}/{arch.n_kv_heads}x{arch.resolved_head_dim} d_ff "
        f"{arch.d_ff} vocab {arch.vocab} {arch.dtype}: {n_params / 1e9:.3f}B "
        f"params, engine built in {time.perf_counter() - t0:.1f}s")

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(SLOTS):
        sampled = i >= SLOTS - SAMPLED
        sp = SamplingParams(temperature=0.8 if sampled else 0.0,
                            top_p=0.95 if sampled else 1.0,
                            seed=seed + i, logprobs=True)
        reqs.append(Request(
            id=i, prompt=rng.integers(1, arch.vocab, PROMPT).astype(np.int32),
            max_new_tokens=NEW, sampling=sp))
    t0 = time.perf_counter()
    outs = engine.generate(reqs)
    wall = time.perf_counter() - t0
    s = engine.metrics.summary()
    log("serve", f"{s['completed']} requests, {s['total_tokens']} tokens, "
        f"{s['prefill_chunks']} prefill chunks, {s['decode_steps']} decode "
        f"steps in {wall:.2f}s wall (compilation included)")
    check("serve", all(o.finish_reason == "length" and o.n_tokens == NEW
                       and len(o.logprobs) == NEW
                       and all(map(math.isfinite, o.logprobs))
                       for o in outs),
          f"all {len(outs)} requests finished with {NEW} tokens and finite "
          f"logprobs")

    dense = jax.jit(lambda p, t: jax.nn.log_softmax(
        T.lm_apply(p, arch, t).logits[0, :, :arch.vocab]
        .astype(jnp.float32)))
    for o in outs[:2]:                           # greedy requests
        ctx = np.concatenate([reqs[o.request_id].prompt,
                              np.asarray(o.token_ids[:-1], np.int32)])
        lp = np.asarray(dense(engine.params, jnp.asarray(ctx)[None]))
        lp = lp[PROMPT - 1:]                     # rows predicting new tokens
        got = lp[np.arange(NEW), o.token_ids]
        dev = float(np.max(np.abs(got - np.asarray(o.logprobs))))
        gap = float(np.max(lp.max(axis=1) - got))
        check("serve", dev <= LOGPROB_TOL and gap <= LOGPROB_TOL,
              f"request {o.request_id}: engine logprobs vs dense forward "
              f"max |diff| {dev:.4f} nats, greedy token below the dense "
              f"argmax by at most {gap:.4f} nats (tol {LOGPROB_TOL}; "
              f"logprob mean {float(got.mean()):.3f}, "
              f"logit spread {float(lp.std(axis=1).mean()):.3f})")
    log("serve", f"peak bytes in use {peak_bytes(jax.devices()[:1])}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_losses(mesh, seed: int, phase: str) -> list:
    """Build the Trainer as launch/train.py does and take STEPS steps."""
    import jax

    from repro.configs import cut_depth, get_arch
    from repro.configs.base import ShapeSpec
    from repro.core.asa import AdaptiveScheduler
    from repro.data import SyntheticLM
    from repro.runtime.trainer import TrainConfig, Trainer

    arch = cut_depth(get_arch("mamba2-780m"), TRAIN_LAYERS)
    shape = ShapeSpec("chip_smoke", SEQ, BATCH, "train")
    trainer = Trainer(
        arch, shape, mesh,
        TrainConfig(lr=1e-3, warmup_steps=1, total_steps=STEPS,
                    checkpoint_every=max(STEPS // 2, 1), seed=seed),
        scheduler=AdaptiveScheduler(faithful=False, opt_preset="adamw32"))
    for line in trainer.plan.summary().splitlines():
        log(phase, line)
    params, opt_state = trainer.init_state()
    data = SyntheticLM(arch.vocab, SEQ, BATCH, seed=seed)
    _, _, hist = trainer.train(
        params, opt_state, data, steps=STEPS, log_every=1,
        on_metrics=lambda s, m: log(
            phase, f"step {s} loss {m['loss']:.5f} "
            f"{m['step_time_s'] * 1e3:.1f} ms"
            + (" (compilation included)" if s == 1 else "")))
    losses = [h["loss"] for h in hist]
    check(phase, len(losses) == STEPS and all(map(math.isfinite, losses)),
          f"{arch.name} on {mesh.devices.size} device(s): {STEPS} steps of "
          f"{BATCH}x{SEQ} tokens, every loss finite")
    return losses


def phase_train(seed: int) -> None:
    from repro.launch.mesh import make_host_mesh

    # no peak-bytes line here: the device's peak spans the whole process,
    # and the serve phase already set it
    train_losses(make_host_mesh(), seed, "train")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import flash_attention as FA
    from repro.kernels import ref
    from repro.kernels import ssd_scan as SSD

    def run(name, fn, args, oracle):
        compiled = jax.jit(fn).lower(*args).compile()
        check("kernels", "tpu_custom_call" in compiled.as_text(),
              f"{name}: compiled program holds the Mosaic kernel")
        outs = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        for label, o, w in zip(("out", "state"), outs, want):
            o, w = np.asarray(o, np.float32), np.asarray(w, np.float32)
            err = float(np.max(np.abs(o - w)) / np.max(np.abs(w)))
            check("kernels", np.all(np.isfinite(o)) and err <= KERNEL_TOL,
                  f"{name} {label} {o.shape}: max error / max |ref| "
                  f"{err:.2e} (tol {KERNEL_TOL})")

    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    # serve geometry: qwen3-8b heads over the engine's max_len
    q, k, v = (jax.random.normal(kk, (1, 32, MAX_LEN, 128), jnp.bfloat16)
               for kk in ks[:3])

    def flash_ref(q, k, v):
        t = (lambda x: x.transpose(0, 2, 1, 3))
        return (t(ref.flash_attention_ref(t(q), t(k), t(v),
                                          scale=128 ** -0.5)),)
    run(f"flash_attention (B,H,S,D)={q.shape} bf16",
        lambda q, k, v: (FA.flash_attention(q, k, v, interpret=False),),
        (q, k, v), flash_ref)

    # train geometry: mamba2-780m's 48 heads of 64, d_state 128, chunk 128
    B, H, S, P, N = BATCH, 48, SEQ, 64, 128
    x = jax.random.normal(ks[3], (B, H, S, P))
    Bm = jax.random.normal(ks[4], (B, H, S, N))
    Cm = jax.random.normal(ks[5], (B, H, S, N))
    dt = jax.nn.softplus(jax.random.normal(ks[6], (B, H, S)))
    a = -jnp.exp(jax.random.normal(ks[7], (B, H, S))) * dt

    def ssd_ref(x, Bm, Cm, dt, a):
        t = (lambda u: jnp.swapaxes(u, 1, 2))
        y, h = ref.ssd_scan_ref(t(x), t(Bm), t(Cm), t(dt), t(a))
        return t(y), h
    run(f"ssd_scan (B,H,S,P)={x.shape} N={N} chunk=128",
        lambda *xs: SSD.ssd_scan(*xs, chunk=128, interpret=False),
        (x, Bm, Cm, dt, a), ssd_ref)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_sharded(seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_host_mesh

    devices = jax.devices()
    check("sharded", len(devices) == 4, f"{len(devices)} devices visible")
    sharded = train_losses(make_host_mesh(), seed, "sharded")
    gc.collect()
    peaks = peak_bytes(devices)
    log("sharded", "peak bytes in use per device after the sharded run: "
        + ", ".join(f"{d.id}: {p}" for d, p in zip(devices, peaks)))
    check("sharded", all(p and p > 0.25 * max(peaks) for p in peaks),
          "state on all four devices (each peak above a quarter of the "
          "largest)")
    single = train_losses(make_host_mesh(1), seed, "one-device")
    rel = float(np.max(np.abs(np.subtract(sharded, single))
                       / np.abs(single)))
    check("sharded", rel <= SHARDED_LOSS_RTOL,
          f"sharded vs one-device losses agree: max relative diff "
          f"{rel:.2e} (tol {SHARDED_LOSS_RTOL}) over {STEPS} steps")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: jax found {dev.platform} ({dev.device_kind})",
              file=sys.stderr)
        return 2
    log("chip", f"{dev.device_kind} x{len(jax.devices())}, compile cache "
        f"{enable_compile_cache()}")
    phases = ([phase_sharded] if args.chips == 4
              else [phase_serve, phase_train, phase_kernels])
    for phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        gc.collect()
        log("chip", f"{phase.__name__} done in "
            f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
