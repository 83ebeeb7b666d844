"""The harness on the CPU: cells dropped in as new files (configuration,
traffic mix, per-layer metric) run through it with no edit to an existing
file, and a run without a chip prints no result."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT


def _run(root, workload, seed=5, seconds=1.0, trace=False):
    from bench import run
    return run.run_cell(workload, seed, seconds, trace, root=root,
                        require_chip=False)


def test_training_cell_from_new_files(tiny_root):
    res = _run(tiny_root, "train.tiny")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_tok_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "update_gap"}


def test_new_metric_is_read_in_a_traced_run(tiny_root):
    res = _run(tiny_root, "train.tiny", trace=True)
    assert set(res["metrics"]) == {"steps_per_s.tiny"}
    assert res["metrics"]["steps_per_s.tiny"]["value"] > 0


def test_serving_cell_from_new_files(tiny_root):
    res = _run(tiny_root, "serve.tiny", seconds=2.0)
    assert res["correct"] is True
    assert {"ttft_p90_ms", "serve_tok_s", "setup_s"} == set(res["metrics"])
    assert res["attempted"] == 8 and res["failed"] == 0
    assert res["checks"]["max_logit_gap"]["value"] < 1e-3


def test_unknown_workload_is_refused(tiny_root):
    with pytest.raises(SystemExit):
        _run(tiny_root, "no.such.cell")


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train.mamba2-16L",
         "--seed", "1", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_every_metric_and_cell_is_wired():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for w in cells.values():
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for c in spec["configs"]:
        assert (ROOT / c["file"]).exists()
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells
            assert "workloads" not in moved or w in moved["workloads"]


def test_queue_wait_is_read_from_the_engines_admissions():
    from bench.drivers.serve import Admissions
    from bench.harness import load_module
    a = Admissions()
    a.request_begin(1, 0.5, prompt_len=3)
    a.request_instant(1, "admitted", 2.0, slot=0)
    a.request_instant(1, "preempt", 3.0)
    a.request_instant(1, "admitted", 4.0, slot=1)     # a re-admission
    a.phase("decode", 4.0, 4.1, n_rows=1)
    assert a.at == {1: 2.0}
    reader = load_module(ROOT / "bench" / "metrics" / "queue_wait_ms.chat.py",
                         "queue_wait_reader")
    assert reader.read({"queue_wait_ms": 12.5}, None, {}) == 12.5
    assert reader.read({}, None, {}) is None
