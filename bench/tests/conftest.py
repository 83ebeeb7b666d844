"""CPU fixtures: a copy of the benchmark with small cells dropped in as new
files and new entries, which no test edits in place."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FIXTURES = BENCH / "tests" / "fixtures"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY = {
    "configs": [
        {"name": "tiny-mamba2", "source": "test fixture",
         "file": "bench/configs/tiny-mamba2.json", "reduced": [],
         "why": "smoke size"},
        {"name": "tiny-qwen3", "source": "test fixture",
         "file": "bench/configs/tiny-qwen3.json", "reduced": [],
         "why": "smoke size"}],
    "workloads": [
        {"name": "train.tiny", "config": "tiny-mamba2", "traffic": "tiny_lm",
         "chips": 1, "why": "smoke size"},
        {"name": "serve.tiny", "config": "tiny-qwen3", "traffic": "tiny_chat",
         "chips": 1, "why": "smoke size"}],
    "per_layer": [
        {"name": "steps_per_s.tiny", "unit": "1/s", "better": "higher",
         "source": "host_clock", "layer": "train step program",
         "moves": "train_tok_s", "workloads": ["train.tiny"]}],
}


def _extend(spec: dict, tiny: dict) -> dict:
    for key, entries in tiny.items():
        spec[key] = spec[key] + entries
    for m in spec["end_to_end"]:
        if "workloads" in m:
            new = [w["name"] for w in tiny["workloads"]
                   if ("train" in m["name"]) == w["name"].startswith("train")]
            m["workloads"] = m["workloads"] + new
    return spec


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    """A checkout with the benchmark's files and the fixture cells added."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for sub in ("configs", "traffic", "metrics"):
        for f in (FIXTURES / sub).iterdir():
            assert not (root / "bench" / sub / f.name).exists()
            shutil.copy(f, root / "bench" / sub / f.name)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "BENCHMARK.json").write_text(json.dumps(_extend(spec, TINY)))
    return root
