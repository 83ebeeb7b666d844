"""Golden generator for the serving parity suite.

Run ONCE against the pre-shim wave Server (commit 17a83e0's
runtime/server.py — batched full-cache prefill + lock-step decode waves) to
freeze its greedy outputs for every scenario in serving_fixtures.SCENARIOS:

    PYTHONPATH=src:tests python tests/gen_serving_goldens.py

The continuous engine's parity tests then compare against the pinned JSON,
NOT against a live wave run — after the wave Server became a shim over the
engine, a live comparison would be circular.  Do not regenerate this file
from a post-shim checkout (it would capture the engine's own outputs and
silently erase the baseline); the checked-in goldens_serving.json is the
falsifiable artifact.

The goldens are greedy-only and stay that way under the v2 generation API:
the default ``SamplingParams()`` is temperature-0 argmax, so every parity
test exercises the new submit/SamplingParams/RequestOutput surface against
these same sequences.  Stochastic decode (temperature > 0) is deliberately
NOT pinned here — the wave Server never sampled, so no baseline exists;
its contract is determinism (bit-identical reruns, invariance under forced
recompute-preemption), pinned by the sampling tests in test_serving.py.

Where the no-cache forward has identical semantics (attention-only, SSM,
hybrid, shared-block and MLA configs), the script also greedy-decodes each
request with plain full-context ``lm_apply`` calls and asserts the wave
Server matched that independent oracle.  (Cross-attn / enc-dec configs are
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
import numpy as np

from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.runtime.server import Request as WaveRequest, Server
from serving_fixtures import (GOLDENS_PATH, SCENARIOS, ARCH_BY_KEY,
                              scenario_requests)

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

        srv = Server(arch, params, mesh, slots=slots, max_len=max_len)
        if hasattr(srv, "engine"):
            sys.exit(
                "REFUSING to regenerate goldens: this checkout's Server is "
                "the post-shim delegate to ContinuousBatchingEngine, so the "
                "output would be the engine's own tokens and every parity "
                "test would become circular.  The checked-in "
                "goldens_serving.json (captured at 17a83e0) is the "
                "baseline; do not overwrite it.")
        for rid, prompt, max_new in reqs:
            srv.submit(WaveRequest(id=rid, prompt=prompt.copy(),
                                   max_new_tokens=max_new))
        srv.run_until_drained()
        wave = {r.id: list(map(int, r.out_tokens)) for r in srv.completed}

        key = SCENARIOS[name]["arch"]
        if key in ORACLE_OK:
            for rid, prompt, max_new in reqs:
                n_new = len(wave[rid])
                ref = reference_decode(params, arch, prompt, n_new)
                assert wave[rid] == ref, (
                    f"{name} req {rid}: wave {wave[rid]} != oracle {ref}")
        scenarios_out[name] = {str(k): v for k, v in sorted(wave.items())}
        print(f"{name}: {[len(v) for v in scenarios_out[name].values()]} "
              f"tokens per request")

    data = {
        "_meta": {
            "source": "pre-shim wave Server (runtime/server.py @ 17a83e0): "
                      "batched full-cache prefill + lock-step decode waves",
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
