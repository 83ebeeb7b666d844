"""The control comes out as not correct: the reference, put in the
program's place and computed in float8 (the precision below the
configurations' bf16), fails one of the cell's checks.  At smoke size on
the CPU; ``bench/control.py`` reads it at the cells' own size on the chip."""
from __future__ import annotations

import json

from conftest import FIXTURES


def _failed(checks) -> list:
    return [c["name"] for c in checks if not c["value"] <= c["limit"]]


def test_training_control_fails():
    import jax

    from bench.drivers import train as D
    from bench.harness import arch_from_config
    from repro.models import transformer as T

    cfg = json.loads((FIXTURES / "configs" / "tiny-mamba2.json").read_text())
    mix = json.loads((FIXTURES / "traffic" / "tiny_lm.json").read_text())
    arch = arch_from_config(cfg)
    abstract = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), arch))
    dev = jax.devices()[:1]
    want = D.reference_readings(cfg, mix, 3, 2, abstract, dev)
    same = D.reference_readings(cfg, mix, 3, 2, abstract, dev)
    assert not _failed(D.checks(cfg, same, want))
    got = D.reference_readings(cfg, mix, 3, 2, abstract, dev, lowp=True)
    assert _failed(D.checks(cfg, got, want))


def test_serving_control_fails(tiny_root, monkeypatch):
    from bench import harness as H
    from bench.drivers import serve as D

    found = {}
    real = D.reference_checks

    def both(cfg, abstract, seed, arrivals, finished):
        found["own"] = real(cfg, abstract, seed, arrivals, finished)
        found["control"] = real(cfg, abstract, seed, arrivals, finished,
                                control=True)
        return found["own"]
    monkeypatch.setattr(D, "reference_checks", both)
    cell = H.load_cell("serve.tiny", tiny_root)
    D.run(H.Run(cell, 4, 2.0, None, H.now()))
    assert not _failed(found["own"])
    assert "max_logit_gap" in _failed(found["control"])
