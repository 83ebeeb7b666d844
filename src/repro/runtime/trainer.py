"""Trainer — the paper's Algorithm 1 as a production loop.

Integrates: ASA planning + periodic re-planning (re-profile -> re-solve ->
reshard -> re-jit), grad-accum microbatching, checkpoint/restart (exact
resume: step, rng, data offset), elastic mesh resize, straggler-aware input
dispatch (data.HostShardedLoader), and live step-time monitoring.

Each step's host phases are spans (core/profiler.py ``Span``) on the
profiler's clock: ``train.put`` (the next batch and its transfer),
``train.dispatch`` (the call of the jitted step until it returns),
``train.sync`` (waiting for the loss, reading the metrics back),
``train.replan`` (the drift monitor, and the planner, reshard and re-jit
when it fires) and ``train.checkpoint``.  A step's metrics carry
``step_time_s`` (dispatch to loss ready) and ``compiles`` (traces and
compiles made during its dispatch).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs.base import ArchConfig, ShapeSpec
from repro.core import sharding as SH
from repro.core.asa import AdaptiveScheduler, SchedulePlan
from repro.core.profiler import Span, compile_count
from repro.launch.mesh import mesh_shape_of
from repro.models import transformer as T
from repro.optim import optimizers as O
from repro.optim.schedules import cosine_schedule
from repro.runtime import steps as ST


@dataclasses.dataclass
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    clip_norm: float = 1.0
    microbatches: int = 0            # 0 = take from the ASA plan
    remat: Optional[str] = None      # None = the policy the plan sized for
    impl: str = "xla"
    checkpoint_every: int = 200
    replan_every: int = 0            # 0 = only on monitor trigger
    quantized_opt: bool = False
    seed: int = 0


class Trainer:
    def __init__(self, arch: ArchConfig, shape: ShapeSpec, mesh,
                 cfg: TrainConfig = TrainConfig(), *,
                 scheduler: Optional[AdaptiveScheduler] = None,
                 checkpoint_dir: Optional[str] = None):
        self.arch, self.shape, self.mesh, self.cfg = arch, shape, mesh, cfg
        self.sched = scheduler or AdaptiveScheduler(faithful=False)
        self.ckpt = (CheckpointManager(checkpoint_dir)
                     if checkpoint_dir else None)
        self.opt = O.adamw(
            cosine_schedule(cfg.lr, cfg.warmup_steps, cfg.total_steps),
            quantized=cfg.quantized_opt)
        self.step = 0
        self.data_offset = 0
        self.plan: Optional[SchedulePlan] = None
        self._jitted = None
        self._replan(init=True)

    # ------------------------------------------------------------------
    def _specs(self):
        ms = mesh_shape_of(self.mesh)
        pspecs = self.plan.param_specs()
        pns = jax.tree.map(lambda s: NamedSharding(self.mesh, s), pspecs)
        act_ns = NamedSharding(
            self.mesh, P(SH.batch_axes(ms, self.shape.global_batch), None, None))
        return pspecs, pns, act_ns

    def _replan(self, init: bool = False):
        ms = mesh_shape_of(self.mesh)
        new_plan = self.sched.plan(self.arch, self.shape, ms)
        changed = (self.plan is None
                   or new_plan.assignment != self.plan.assignment)
        self.plan = new_plan
        if not (changed or init):
            return False
        pspecs, pns, act_ns = self._specs()
        mb = self.cfg.microbatches or self.plan.microbatches
        step_fn = ST.make_train_step(
            self.arch, self.opt, microbatches=mb, impl=self.cfg.impl,
            remat=self.cfg.remat or self.sched.remat, act_sharding=act_ns,
            clip_norm=self.cfg.clip_norm)
        def opt_specs_fn(osds):
            return SH.opt_state_specs(osds, pspecs, ms)
        self._jitted = None          # rebuilt lazily with opt specs
        self._step_fn, self._pns, self._opt_specs_fn = step_fn, pns, opt_specs_fn
        return changed

    def _opt_shardings(self, opt_state):
        opt_sds = jax.eval_shape(lambda o: o, opt_state)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self._opt_specs_fn(opt_sds))

    def _jit(self, params, opt_state):
        self._jitted = ST.jit_step(
            "train", self._step_fn,
            out_shardings=(self._pns, self._opt_shardings(opt_state), None))

    # ------------------------------------------------------------------
    def init_state(self, rng: Optional[jax.Array] = None):
        rng = rng if rng is not None else jax.random.PRNGKey(self.cfg.seed)
        _, pns, _ = self._specs()
        params = jax.jit(
            lambda k: T.init_lm(k, self.arch), out_shardings=pns)(rng)
        opt_init, _ = self.opt
        # born with the shardings the step returns, so step 2 reuses the
        # program step 1 compiled
        opt_state = jax.jit(opt_init, out_shardings=self._opt_shardings(
            jax.eval_shape(opt_init, params)))(params)
        return params, opt_state

    def maybe_restore(self, params, opt_state):
        """Restart-from-checkpoint (reshards to the current mesh)."""
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return params, opt_state
        _, pns, _ = self._specs()
        state = {"params": params, "opt": opt_state}
        sh = {"params": pns,
              "opt": jax.tree.map(lambda _: NamedSharding(self.mesh, P()),
                                  opt_state)}
        restored, manifest = self.ckpt.restore(state, shardings=sh)
        self.step = manifest["step"]
        self.data_offset = manifest.get("data_offset", self.step)
        return restored["params"], restored["opt"]

    # ------------------------------------------------------------------
    def train(self, params, opt_state, data_iter, *, steps: int,
              log_every: int = 10, on_metrics: Optional[Callable] = None):
        if self._jitted is None:
            self._jit(params, opt_state)
        # each device gets only its rows of the batch (leading axis)
        batch_ns = NamedSharding(self.mesh, P(SH.batch_axes(
            mesh_shape_of(self.mesh), self.shape.global_batch)))
        metrics_hist = []
        for _ in range(steps):
            with Span("train.put", step=self.step + 1):
                batch = jax.device_put(dict(next(data_iter)), batch_ns)
            compiles0 = compile_count()
            t0 = time.perf_counter()
            with Span("train.dispatch") as sp:
                params, opt_state, metrics = self._jitted(params, opt_state,
                                                          batch)
                compiles = compile_count() - compiles0
                sp.set(compiles=compiles)
            with Span("train.sync"):
                jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0
                m = {k: float(v) for k, v in metrics.items()}
            self.step += 1
            self.data_offset += 1

            with Span("train.replan"):
                if self.sched.record_step(dt) or (
                        self.cfg.replan_every
                        and self.step % self.cfg.replan_every == 0):
                    if self._replan():     # strategy switch: reshard + re-jit
                        params = jax.device_put(params, self._pns)
                        self._jit(params, opt_state)

            if self.ckpt and self.step % self.cfg.checkpoint_every == 0:
                with Span("train.checkpoint"):
                    self.ckpt.save(self.step,
                                   {"params": params, "opt": opt_state},
                                   extra={"data_offset": self.data_offset})
            m["step_time_s"] = dt
            m["compiles"] = compiles
            metrics_hist.append(m)
            if on_metrics and self.step % log_every == 0:
                on_metrics(self.step, m)
        return params, opt_state, metrics_hist

    # ------------------------------------------------------------------
    def resize(self, new_mesh, params, opt_state):
        """Elastic rescale: re-plan on the new mesh and reshard live state."""
        self.mesh = new_mesh
        self._replan(init=True)
        _, pns, _ = self._specs()
        params = jax.device_put(params, pns)
        # optimizer state: reshard step scalar + moments like params
        opt_state = jax.device_put(opt_state,
                                   self._opt_shardings(opt_state))
        self._jit(params, opt_state)
        return params, opt_state
