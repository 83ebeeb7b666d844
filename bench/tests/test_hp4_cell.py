"""The four-chip training cell ``train.mamba2-48L.hp4`` on the CPU: its
files found by name, the short traced window of its driver, the reader of
the collectives' share of the window, the ASA plan at the cell's own size,
and a tiny model through the same driver on four virtual devices."""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import textwrap

import pytest

from conftest import FIXTURES, ROOT

CELL = "train.mamba2-48L.hp4"


def test_cell_is_the_whole_model_at_published_widths():
    from bench.harness import arch_from_config, load_cell
    from repro.configs import get_arch

    cell = load_cell(CELL)
    assert cell.chips == 4
    assert cell.traffic["driver"] == "train_short_trace"
    assert cell.config["reduced"] == []
    arch = arch_from_config(cell.config)
    assert arch.n_layers == get_arch("mamba2-780m").n_layers == 48
    s = arch.ssm
    assert (arch.d_model, arch.vocab, arch.tie_embeddings) == \
        (1536, 50277, True)
    assert (s.d_state, s.head_dim, s.expand, s.n_groups, s.d_conv,
            s.chunk) == (128, 64, 2, 1, 4, 256)
    assert s.expand * arch.d_model // s.head_dim == 48      # SSD heads


def test_plan_divides_the_layers_over_four_chips():
    """At the cell's size the plan on one chip is infeasible.  On the
    four-chip mesh the layers' large projections (and so their gradients
    and AdamW moments, which mirror them) are split four ways along data,
    and a chip holds about a third of the parameters, not a replica: the
    plan keeps the embedding whole on each chip (DP), and ``leaf_spec``
    has no split for the small B and C projections."""
    import jax
    from jax.sharding import PartitionSpec

    from bench.harness import arch_from_config, load_cell
    from repro.configs.base import ShapeSpec
    from repro.core.asa import AdaptiveScheduler
    from repro.core.costmodel import MeshShape
    from repro.core.sharding import abstract_params

    cell = load_cell(CELL)
    arch = arch_from_config(cell.config)
    sched = AdaptiveScheduler(faithful=False, opt_preset="adamw32")
    rows = cell.traffic["batch_per_chip"]
    one = sched.plan(arch, ShapeSpec(CELL, 1024, rows, "train"),
                     MeshShape(data=1, model=1))
    assert not one.plan.feasible
    plan = sched.plan(arch, ShapeSpec(CELL, 1024, 4 * rows, "train"),
                      MeshShape(data=4, model=1))
    assert plan.plan.feasible
    shapes = jax.tree_util.tree_flatten_with_path(abstract_params(arch))[0]
    specs = jax.tree.leaves(plan.param_specs(),
                            is_leaf=lambda x: isinstance(x, PartitionSpec))
    whole = here = 0
    for (path, sds), spec in zip(shapes, specs):
        n = math.prod(sds.shape)
        split = 4 if "data" in jax.tree.leaves(tuple(spec)) else 1
        if "segments" in str(path[0]) and n >= 1 << 24:
            assert split == 4, (path, spec)
        whole, here = whole + n, here + n / split
    assert here < 0.35 * whole


@pytest.mark.parametrize("trace_dir,seconds,expect", [
    (None, 51.0, 51.0), ("trace", 51.0, 10.0), ("trace", 4.0, 4.0)])
def test_short_trace_bounds_only_the_traced_window(monkeypatch, tmp_path,
                                                   trace_dir, seconds,
                                                   expect):
    from bench import harness as H
    from bench.drivers import train, train_short_trace

    passed = []
    monkeypatch.setattr(train, "run", passed.append)
    d = None if trace_dir is None else tmp_path / trace_dir
    given = H.Run(H.load_cell(CELL), 7, seconds, d, 0.0)
    train_short_trace.run(given)
    assert passed[0].seconds == expect
    assert passed[0].trace_dir == d and passed[0].cell is given.cell
    if d is None:
        assert passed[0] is given


@pytest.mark.parametrize("trace,expect", [
    (None, None),
    ({"devices": 1, "window_s": 10.0, "collective_s": 0.0,
      "collective_exposed_s": 0.0}, None),
    ({"devices": 4, "window_s": 10.0, "collective_s": 1.25,
      "collective_exposed_s": 0.25}, 12.5)])
def test_collective_share_reader(trace, expect):
    from bench.harness import load_module
    reader = load_module(
        ROOT / "bench" / "metrics" / "collective_share.hp4.py",
        "collective_share_reader")
    assert reader.read({"chips": 4}, trace, {}) == expect


def test_tiny_model_through_the_driver_on_four_devices(tiny_root):
    """The tiny configuration under the cell's driver on four virtual CPU
    devices: the sharded step agrees with the reference spread over the
    same devices."""
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    if "train.tiny4.short" not in {w["name"] for w in spec["workloads"]}:
        mix = json.loads((FIXTURES / "traffic" / "tiny_lm.json").read_text())
        mix.update(driver="train_short_trace", trace_seconds=1)
        (tiny_root / "bench" / "traffic" / "tiny_lm.trace1.json").write_text(
            json.dumps(mix))
        spec["workloads"].append({"name": "train.tiny4.short",
                                  "config": "tiny-mamba2",
                                  "traffic": "tiny_lm.trace1", "chips": 4,
                                  "why": "smoke size"})
        for m in spec["end_to_end"]:
            if m["name"] == "train_tok_s":
                m["workloads"].append("train.tiny4.short")
        (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    code = textwrap.dedent(f"""
        import json, pathlib, sys
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
        from bench import run
        res = run.run_cell("train.tiny4.short", 2147491601, 1.0, False,
                           root=pathlib.Path({str(tiny_root)!r}),
                           require_chip=False)
        print("RESULT", json.dumps(res))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, p.stderr[-3000:]
    res = json.loads(line[-1][len("RESULT "):])
    assert res["device"]["count"] == 4
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["metrics"]["train_tok_s"]["value"] > 0
