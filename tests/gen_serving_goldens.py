"""Golden generator for the serving parity suite.

The checked-in goldens_serving.json was frozen from the retired wave Server
(commit 17a83e0's runtime/server.py — batched full-cache prefill +
lock-step decode waves): its greedy outputs for every scenario in
serving_fixtures.SCENARIOS.  The continuous engine's parity tests compare
against that pinned JSON.  This script drives ContinuousBatchingEngine over
the same scenarios, so its output would be the engine's own tokens and a
comparison against it circular: it refuses to overwrite an existing
goldens file, and writes one only where none is checked in.

    PYTHONPATH=src:tests python tests/gen_serving_goldens.py

The goldens are greedy-only and stay that way under the v2 generation API:
the default ``SamplingParams()`` is temperature-0 argmax, so every parity
test exercises the new submit/SamplingParams/RequestOutput surface against
these same sequences.  Stochastic decode (temperature > 0) is deliberately
NOT pinned here — the wave Server never sampled, so no baseline exists;
its contract is determinism (bit-identical reruns, invariance under forced
recompute-preemption), pinned by the sampling tests in test_serving.py.

Where the no-cache forward has identical semantics (attention-only, SSM,
hybrid, shared-block and MLA configs), the script also greedy-decodes each
request with plain full-context ``lm_apply`` calls and asserts the engine
matches that independent oracle.  (Cross-attn / enc-dec configs are
excluded from the oracle: without a cache the cross-attention falls back to
self-attention, which is not what serving-with-zero-cross-K/V computes.)
"""
from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "tests"))

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.serving import ContinuousBatchingEngine, Request
from serving_fixtures import GOLDENS_PATH, SCENARIOS, scenario_requests

# configs whose cache-free forward equals the serving computation (oracle)
ORACLE_OK = {"tiny", "ssm", "hybrid", "shared", "mla"}


def reference_decode(params, arch, prompt, n_new: int) -> list[int]:
    ctx = list(int(t) for t in prompt)
    out = []
    for _ in range(n_new):
        logits = T.lm_apply(params, arch,
                            jnp.asarray([ctx], jnp.int32)).logits
        nxt = int(jnp.argmax(logits[0, -1, : arch.vocab]))
        out.append(nxt)
        ctx.append(nxt)
    return out


def main():
    if GOLDENS_PATH.exists():
        sys.exit(
            f"REFUSING to regenerate goldens: {GOLDENS_PATH} holds the "
            f"retired wave Server's outputs (captured at 17a83e0), the "
            f"baseline every parity test compares the engine with; "
            f"overwriting it with the engine's own tokens would make them "
            f"circular.")
    mesh = make_host_mesh()
    params_cache = {}
    scenarios_out = {}
    for name in SCENARIOS:
        arch, reqs, slots, max_len = scenario_requests(name)
        if arch.name not in params_cache:
            # the goldens' weights come from the non-partitionable threefry
            # PRNG (jax's default when they were recorded); the parity
            # tests pin the same mode
            with jax.threefry_partitionable(False):
                params_cache[arch.name] = T.init_lm(jax.random.PRNGKey(0),
                                                    arch)
        params = params_cache[arch.name]

        eng = ContinuousBatchingEngine(arch, params, mesh, slots=slots,
                                       max_len=max_len)
        for rid, prompt, max_new in reqs:
            eng.submit(Request(id=rid, prompt=prompt.copy(),
                               max_new_tokens=max_new))
        eng.run_until_drained()
        got = {o.request_id: list(map(int, o.token_ids))
               for o in eng.completed}

        key = SCENARIOS[name]["arch"]
        if key in ORACLE_OK:
            for rid, prompt, max_new in reqs:
                n_new = len(got[rid])
                ref = reference_decode(params, arch, prompt, n_new)
                assert got[rid] == ref, (
                    f"{name} req {rid}: engine {got[rid]} != oracle {ref}")
        scenarios_out[name] = {str(k): v for k, v in sorted(got.items())}
        print(f"{name}: {[len(v) for v in scenarios_out[name].values()]} "
              f"tokens per request")

    data = {
        "_meta": {
            "source": "ContinuousBatchingEngine (tests/"
                      "gen_serving_goldens.py)",
            "params": "T.init_lm(jax.random.PRNGKey(0), arch), float32",
            "oracle_checked": sorted(ORACLE_OK),
        },
        "scenarios": scenarios_out,
    }
    with open(GOLDENS_PATH, "w") as f:
        json.dump(data, f, indent=1)
        f.write("\n")
    print(f"-> {GOLDENS_PATH}")


if __name__ == "__main__":
    main()
