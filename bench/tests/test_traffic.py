"""The traffic generator: the same seed gives the same inputs, and every
seed gets the same set of sizes and arrivals, in another order or, where the
mix fixes it, in the same one."""
from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import ROOT


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def test_same_seed_same_requests():
    from bench.traffic import requests
    mix = _mix("chat")
    x, y = ([(r.due_s, r.prompt.tolist(), r.max_new_tokens)
             for r in requests(mix, 2**31 + 17, 40.0, 151936)]
            for _ in range(2))
    assert x == y


@pytest.mark.parametrize("order", ["seed", "fixed"])
def test_seeds_share_sizes_and_arrivals(order):
    from bench.traffic import requests
    mix = dict(_mix("chat"), order=order)
    runs = [requests(mix, s, 40.0, 151936) for s in (1, 2, 2**33 + 5)]
    assert len({len(r) for r in runs}) == 1
    assert len(runs[0]) == round(mix["rate_per_s"] * 40.0)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        sizes = [sorted(map(key, r)) for r in runs]
        assert sizes[0] == sizes[1] == sizes[2]
    orders = [[(len(r.prompt), r.max_new_tokens, r.due_s) for r in run]
              for run in runs]
    if order == "seed":
        assert orders[0] != orders[1]
    else:
        assert orders[0] == orders[1] == orders[2]
    assert runs[0][0].prompt.tolist() != runs[1][0].prompt.tolist()
    for run in runs:
        due = np.array([r.due_s for r in run])
        assert due[0] == 0 and np.all(np.diff(due) >= 0) and due[-1] < 40.0
        assert all(1 <= r.prompt.min() and r.prompt.max() < 151936
                   for r in run)
        lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
        assert all(lo <= len(r.prompt) <= hi for r in run)


def test_training_rows_differ_and_repeat():
    from bench.traffic import train_batch
    mix = _mix("lm_8x1024")
    a = train_batch(mix, 2**32 + 3, 0, 8, 50277)
    b = train_batch(mix, 2**32 + 3, 0, 8, 50277)
    c = train_batch(mix, 2**32 + 3, 1, 8, 50277)
    assert a["tokens"].shape == (8, 1024)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert len({row.tobytes() for row in a["tokens"]}) == 8
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
