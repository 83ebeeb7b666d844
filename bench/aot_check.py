"""Rehearsal without a chip: compile each cell's step programs for a
described TPU v5e host (``v5e:2x2``) and print what ``memory_analysis()``
gives per device.  Nothing runs, so this says nothing about time.

    JAX_PLATFORMS=cpu python bench/aot_check.py [--cells a,b] [--one-device]

``--one-device`` also compiles each four-chip training cell on one chip of
the host, to show whether one chip could hold it.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

GB = 1e9


def _mesh(devices):
    import numpy as np
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(devices).reshape(len(devices), 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _sds(tree, shardings):
    import jax
    return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), tree, shardings)


def _report(name, compiled) -> dict:
    m = compiled.memory_analysis()
    out = {"program": name,
           "argument_gb": m.argument_size_in_bytes / GB,
           "output_gb": m.output_size_in_bytes / GB,
           "alias_gb": m.alias_size_in_bytes / GB,
           "temp_gb": m.temp_size_in_bytes / GB}
    out["total_gb"] = (m.argument_size_in_bytes + m.output_size_in_bytes
                       - m.alias_size_in_bytes + m.temp_size_in_bytes) / GB
    print(json.dumps(out), flush=True)
    return out


def train_step(cell, devices):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench.harness import arch_from_config
    from repro.configs.base import ShapeSpec
    from repro.core import sharding as SH
    from repro.core.asa import AdaptiveScheduler
    from repro.launch.mesh import mesh_shape_of
    from repro.models import transformer as T
    from repro.runtime.trainer import TrainConfig, Trainer

    cfg, mix = cell.config, cell.traffic
    arch = arch_from_config(cfg)
    rows = mix["batch_per_chip"] * cell.chips
    mesh = _mesh(devices)
    tr = Trainer(arch, ShapeSpec(cell.name, mix["seq_len"], rows, "train"),
                 mesh, TrainConfig(),
                 scheduler=AdaptiveScheduler(faithful=False,
                                             opt_preset="adamw32"))
    print(f"# {cell.name} on {len(devices)} device(s): "
          f"{tr.plan.summary().splitlines()[0]}", flush=True)
    pns = jax.tree.map(lambda s: NamedSharding(mesh, s),
                       tr.plan.param_specs())
    params = _sds(jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0),
                                                   arch)), pns)
    opt = jax.eval_shape(tr.opt[0], params)
    opt = _sds(opt, tr._opt_shardings(opt))
    bns = NamedSharding(mesh, P(SH.batch_axes(mesh_shape_of(mesh), rows)))
    tok = jax.ShapeDtypeStruct((rows, mix["seq_len"]), "int32", sharding=bns)
    tr._jit(params, opt)
    return _report(f"{cell.name} train step x{len(devices)}",
                   tr._jitted.lower(params, opt, {"tokens": tok,
                                                  "labels": tok}).compile())


def serve_steps(cell, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from bench.harness import arch_from_config
    from repro.models import transformer as T
    from repro.runtime import steps as ST
    from repro.serving.engine import serving_plan
    from repro.serving.paged_cache import blocks_for
    from repro.serving.sampling import make_sampler

    sv = cell.config["serving"]
    arch = arch_from_config(cell.config)
    mesh = _mesh(devices)
    plan = serving_plan(arch, mesh, slots=sv["slots"], max_len=sv["max_len"])
    pns = jax.tree.map(lambda s: NamedSharding(mesh, s), plan.param_specs())
    params = _sds(jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0),
                                                   arch)), pns)
    mb = blocks_for(sv["max_len"], sv["block_size"])
    pools = jax.eval_shape(lambda: T.init_paged_cache(
        arch, sv.get("num_blocks") or sv["slots"] * mb + 1, sv["block_size"],
        jnp.bfloat16))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    pools = _sds(pools, jax.tree.map(lambda _: rep, pools))

    def vec(n, dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=rep)
    sampler = make_sampler(arch.vocab)
    out = []
    for kind, B, S, make in (
            ("paged_prefill", 1, sv["prefill_chunk"],
             ST.make_paged_prefill_step),
            ("paged_decode", sv["slots"], 1, ST.make_paged_decode_step)):
        fn = ST.jit_step(kind, make(arch, sampler=sampler))
        args = [params, pools,
                jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=rep),
                vec(B, jnp.int32),
                jax.ShapeDtypeStruct((B, mb), jnp.int32, sharding=rep)]
        if kind == "paged_prefill":
            args.append(vec(B, jnp.int32))
        args += [vec(B, jnp.int32), vec(B, jnp.float32), vec(B, jnp.int32),
                 vec(B, jnp.float32), vec(B, jnp.uint32)]
        out.append(_report(f"{cell.name} {kind} ({B}, {S})",
                           fn.lower(*args).compile()))
    return out


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies

    from bench.harness import load_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="")
    ap.add_argument("--one-device", action="store_true")
    a = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = a.cells.split(",") if a.cells else \
        [w["name"] for w in spec["workloads"]]
    for name in names:
        cell = load_cell(name)
        devices = topo.devices[:cell.chips]
        if cell.traffic["driver"] == "train":
            if a.one_device and cell.chips > 1:
                try:
                    train_step(cell, topo.devices[:1])
                except Exception as e:        # the compiler's refusal
                    print(f"# {name} on one device: {type(e).__name__}: "
                          f"{str(e).splitlines()[0][:400]}", flush=True)
            train_step(cell, devices)
        else:
            serve_steps(cell, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
