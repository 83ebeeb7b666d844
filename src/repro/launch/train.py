"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
        --steps 50 [--checkpoint-dir ckpts] [--opt8bit]

--smoke uses the reduced same-family config (CPU-runnable); without it the
full config is planned on the production mesh (requires real hardware or
the dry-run's virtual devices).
"""
from __future__ import annotations

import argparse


from repro.configs import ARCHS, get_arch, reduce_for_smoke
from repro.configs.base import ShapeSpec
from repro.core.asa import AdaptiveScheduler
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.runtime.trainer import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--opt8bit", action="store_true")
    ap.add_argument("--replan-every", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    arch = get_arch(args.arch)
    if args.smoke:
        arch = reduce_for_smoke(arch)
    shape = ShapeSpec("cli", args.seq_len, args.batch, "train")
    mesh = make_host_mesh()
    sched = AdaptiveScheduler(
        faithful=False,
        opt_preset="adamw8bit" if args.opt8bit else "adamw32")
    trainer = Trainer(
        arch, shape, mesh,
        TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                    total_steps=args.steps, replan_every=args.replan_every,
                    quantized_opt=args.opt8bit,
                    checkpoint_every=max(args.steps // 2, 1)),
        scheduler=sched, checkpoint_dir=args.checkpoint_dir)
    print(trainer.plan.summary())

    params, opt_state = trainer.init_state()
    if args.checkpoint_dir:
        params, opt_state = trainer.maybe_restore(params, opt_state)
    data = SyntheticLM(arch.vocab, args.seq_len, args.batch,
                       start_step=trainer.data_offset)
    params, opt_state, hist = trainer.train(
        params, opt_state, data, steps=args.steps,
        on_metrics=lambda s, m: print(
            f"step {s:5d}  loss {m['loss']:.4f}  "
            f"{m['step_time_s']*1e3:.0f} ms"))
    print(f"done: loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
    if trainer.ckpt:
        trainer.ckpt.wait()


if __name__ == "__main__":
    main()
