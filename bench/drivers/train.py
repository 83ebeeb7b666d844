"""The trainer's entry point: ``Trainer.train`` under its ASA plan.

Set-up builds one Trainer, gives it the seed's weights, and drives it
through its first three steps with the window's own call and feed; those
steps are what the reference follows.  The window then calls the same
trainer, one step a call, until ``seconds`` have passed.  After the window
the program's state is freed and the reference takes the same three steps.
"""
from __future__ import annotations

import gc

from bench import compare
from bench.harness import (Capture, Phases, Run, arch_from_config, log,
                           memory_peak_bytes, now, span, traced)

CHECKED_STEPS = 3


def _feed(mix, seed, rows, vocab):
    from bench.traffic import train_batch
    step = 0
    while True:
        yield train_batch(mix, seed, step, rows, vocab)
        step += 1


def call_times(calls, steps) -> str:
    """The window's calls in brief: median, slowest, and those that took a
    tenth longer than the median (a stall shows here and not in a rate),
    each with the trainer's own time from dispatch to the loss (the rest
    of a call is the batch's transfer and the host)."""
    import numpy as np
    if not calls:
        return "no calls"
    c = np.asarray(calls)
    med = float(np.median(c))
    slow = np.flatnonzero(c > 1.1 * med)
    return (f"call median {1e3 * med:.1f} ms, max {1e3 * c.max():.1f} ms; "
            f"{len(slow)} over 1.1x median "
            f"({1e3 * float(c[slow].sum() - med * len(slow)):.0f} ms extra): "
            + ", ".join(f"#{i} {1e3 * c[i]:.0f} ms (step {1e3 * steps[i]:.0f})"
                        for i in slow[:8]))


def run(r: Run) -> dict:
    import jax
    import numpy as np

    from bench import weights as W
    from bench.flops import mamba2_train_flops_per_token
    from repro.configs.base import ShapeSpec
    from repro.core.asa import AdaptiveScheduler
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.trainer import TrainConfig, Trainer

    cfg, mix, chips = r.cell.config, r.cell.traffic, r.cell.chips
    opt = cfg["optimizer"]
    arch = arch_from_config(cfg)
    rows, seq = mix["batch_per_chip"] * chips, mix["seq_len"]
    devices = jax.devices()[:chips]
    phases = Phases(r.t_start)
    phases.mark("start")
    trainer = Trainer(
        arch, ShapeSpec(r.cell.name, seq, rows, "train"),
        make_host_mesh(chips),
        TrainConfig(lr=opt["lr"], warmup_steps=opt["warmup_steps"],
                    total_steps=opt["total_steps"],
                    clip_norm=opt["clip_norm"], seed=r.seed & 0x7FFFFFFF),
        scheduler=AdaptiveScheduler(faithful=False, opt_preset="adamw32"))
    log(f"plan: {trainer.plan.summary().splitlines()[0]}")
    phases.mark("plan")
    # the trainer's own state, with the seed's weights in its shardings
    prog_params, opt_state = trainer.init_state()
    shardings = jax.tree.map(lambda x: x.sharding, prog_params)
    abstract = jax.eval_shape(lambda: prog_params)
    del prog_params
    params = W.make_params(abstract, r.seed, shardings)
    jax.block_until_ready((params, opt_state))
    phases.mark("state")
    feed = _feed(mix, r.seed, rows, arch.vocab)

    step_times = []         # the trainer's own: dispatch to loss ready

    def call():
        nonlocal params, opt_state
        with span("train.call"):
            params, opt_state, hist = trainer.train(
                params, opt_state, feed, steps=1, log_every=1 << 30)
        step_times.append(hist[0]["step_time_s"])
        return hist[0]["loss"]

    losses = [call()]
    phases.mark("first step")
    step_fn = trainer._jitted = Capture(trainer._jitted)
    grad1 = {k: v / (1 - opt["b1"])
             for k, v in compare.leaf_norms(opt_state.mu).items()}
    losses += [call() for _ in range(CHECKED_STEPS - 1)]
    phases.mark("checked steps")
    key = W.seed_key(r.seed)
    build = W.builder(abstract)
    # the key is an argument, not a constant, so every seed's program is
    # the same one and comes from the compile cache
    delta = jax.jit(
        lambda p, k: jax.tree.map(lambda a, b: a - b, p, build(k)))(
            params, key)
    jax.block_until_ready(delta)
    phases.mark("change")
    change = compare.leaf_norms(delta)
    del delta
    phases.mark("change norms")
    setup_s = now() - r.t_start
    log(f"set-up {setup_s:.1f}s ({phases}); losses {losses}")

    calls = []
    del step_times[:]
    with traced(r.trace_dir):
        t0 = now()
        with span("bench.window"):
            while (t := now()) - t0 < r.seconds:
                call()
                calls.append(now() - t)
        t1 = now()
    window, steps = t1 - t0, len(calls)
    tokens = steps * rows * seq
    log(f"window {window:.3f}s: {steps} steps, {tokens / window:.1f} tokens/s; "
        f"{call_times(calls, step_times)}")

    peak, estimate = memory_peak_bytes(devices, [step_fn])
    del params, opt_state, trainer, step_fn, feed
    gc.collect()

    t_ref = now()
    found = checks(cfg, (losses, grad1, change),
                   reference_readings(cfg, mix, r.seed, rows, abstract,
                                      devices))
    log(f"reference {now() - t_ref:.1f}s")
    return {
        "attempted": steps, "failed": 0,
        "end_to_end": {"train_tok_s": tokens / window, "setup_s": setup_s},
        "record": {"window_s": window, "steps": steps, "tokens": tokens,
                   "chips": chips, "memory_estimate_bytes": estimate,
                   "flops_per_token": mamba2_train_flops_per_token(cfg)},
        "checks": found,
        "memory_peak_bytes": peak,
    }


def spread(devices):
    """-> place(x): an array laid over ``devices``, split along its last
    axis that they divide (rows of a batch: the first), so that the
    reference's state fits where the program's did."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("d",))
    n = len(devices)

    def place(x, axis=None):
        if axis is None:
            axis = next((a for a in reversed(range(x.ndim))
                         if x.shape[a] % n == 0), None)
        spec = [None] * x.ndim
        if axis is not None:
            spec[axis] = "d"
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))
    return place


def reference_readings(cfg, mix, seed, rows, abstract, devices,
                       lowp=False, grad_rows=None):
    """The reference's three steps from the seed's weights and rows, over
    ``devices``: (losses, first clipped gradient's leaf norms, leaf norms of
    the change).  ``lowp`` computes in float8 (the control); ``grad_rows``
    keeps only the first rows of each batch (a planted fault)."""
    import jax
    import jax.numpy as jnp

    from bench import weights as W
    from bench.reference import mamba2 as ref
    from bench.traffic import train_batch

    place = spread(devices)
    f32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                       abstract)
    w0 = jax.tree.map(place, ref.weights(W.named_leaves(
        W.make_params(f32, seed))))
    batches = [train_batch(mix, seed, s, rows, cfg["vocab_size"])
               for s in range(CHECKED_STEPS)]
    batches = [(place(b["tokens"], 0), place(b["labels"], 0))
               for b in batches]
    losses, g1, w = ref.train(w0, cfg, cfg["optimizer"], batches, lowp=lowp,
                              grad_rows=grad_rows,
                              block=mix["batch_per_chip"])
    change = jax.tree.map(lambda a, b: a - b, w, w0)
    del w0, w
    return (losses, compare.leaf_norms(ref.by_path(g1)),
            compare.leaf_norms(ref.by_path(change)))


def checks(cfg, got, want) -> list:
    """Each compared number beside its limit: ``got`` (the program's
    readings) against ``want`` (the reference's): the first step's loss,
    the first clipped gradient and the parameters' change over the checked
    steps.  The loss gap over all the checked steps is logged: the later
    steps' losses swing (PERF.md)."""
    losses, grad1, change = got
    r_losses, r_grad1, r_change = want
    g_gap, g_at = compare.norm_gap(grad1, r_grad1, r_grad1)
    u_gap, u_at = compare.norm_gap(change, r_change, r_grad1)
    log(f"reference losses {r_losses}; loss gap over the steps "
        f"{compare.loss_gap(losses, r_losses)!r}; worst gradient leaf "
        f"{g_at}, worst change leaf {u_at}")
    lim = cfg["limits"]
    return [{"name": "loss_gap", "value": compare.loss_gap(losses[:1],
                                                           r_losses[:1]),
             "limit": lim["loss_gap"]},
            {"name": "grad_gap", "value": g_gap, "limit": lim["grad_gap"]},
            {"name": "update_gap", "value": u_gap,
             "limit": lim["update_gap"]}]
