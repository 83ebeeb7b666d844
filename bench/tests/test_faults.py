"""A run with the timed path broken underneath comes out not correct, once
for each fault the cells can have.  The harness's look for a chip is
skipped; the rest of the run is the one the chip runs, at smoke size."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from conftest import ROOT


def _run(root, workload, seconds=1.0):
    from bench import run
    return run.run_cell(workload, 11, seconds, False, root=root,
                        require_chip=False)


def _failed(res) -> list:
    return [k for k, c in res["checks"].items() if not c["value"] <= c["limit"]]


def _broken_train_step(monkeypatch, fault):
    from repro.runtime import steps as ST
    real = ST.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def broken(params, opt_state, batch):
            if fault == "unchanged":
                _, _, m = step(params, opt_state, batch)
                return params, opt_state, m
            if fault == "params_unchanged":      # the moments move, not
                _, new_opt, m = step(params, opt_state, batch)  # the weights
                return params, new_opt, m
            rows = next(iter(batch.values())).shape[0] // 2
            return step(params, opt_state,
                        {k: v[:rows] for k, v in batch.items()})
        return broken
    monkeypatch.setattr(ST, "make_train_step", make)


@pytest.mark.parametrize("fault,caught_by", [
    ("unchanged", "grad_gap"), ("half_batch", "grad_gap"),
    ("params_unchanged", "update_gap")])
def test_training_fault_is_caught(tiny_root, monkeypatch, fault, caught_by):
    _broken_train_step(monkeypatch, fault)
    res = _run(tiny_root, "train.tiny")
    assert res["correct"] is False, res["checks"]
    assert caught_by in _failed(res)


def test_serving_token_altered_is_caught(tiny_root, monkeypatch):
    from repro.serving.engine import ContinuousBatchingEngine
    real = ContinuousBatchingEngine._record_token

    def altered(self, slot, tok, logp):
        if len(slot.req.out_tokens) == 1:        # each request's 2nd token
            tok = (tok + self.arch.vocab // 2) % self.arch.vocab
        return real(self, slot, tok, logp)
    monkeypatch.setattr(ContinuousBatchingEngine, "_record_token", altered)
    res = _run(tiny_root, "serve.tiny", seconds=2.0)
    assert res["correct"] is False
    assert "max_logit_gap" in _failed(res)


def test_exchange_between_chips_left_out(tiny_root):
    """Four virtual CPU devices, the tiny cell on all four: the gradient
    the optimizer gets is the first chip's own, as if the all-reduce were
    gone."""
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    if "train.tiny4" not in {w["name"] for w in spec["workloads"]}:
        spec["workloads"].append({"name": "train.tiny4",
                                  "config": "tiny-mamba2",
                                  "traffic": "tiny_lm", "chips": 4,
                                  "why": "smoke size"})
        (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    code = textwrap.dedent(f"""
        import sys
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
        import pathlib, jax
        assert len(jax.devices()) == 4
        from repro.runtime import steps as ST
        from bench import run
        real = ST.make_train_step
        def make(*a, **kw):
            step = real(*a, **kw)
            def local(params, opt_state, batch):
                rows = next(iter(batch.values())).shape[0] // 4
                return step(params, opt_state,
                            {{k: v[:rows] for k, v in batch.items()}})
            return local
        ST.make_train_step = make
        root = pathlib.Path({str(tiny_root)!r})
        ok = run.run_cell("train.tiny4", 11, 1.0, False, root=root,
                          require_chip=False)["correct"]
        print("CORRECT", ok)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "CORRECT False" in p.stdout, p.stderr[-3000:]
