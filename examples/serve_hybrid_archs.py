"""Continuous serving for the hybrid and encoder-decoder architectures.

zamba2 (weight-shared attention block over a Mamba2 backbone) and whisper
(encoder-decoder) run on ContinuousBatchingEngine like every other config:

  * zamba2: the shared block's KV pages through a per-application block
    pool (one pool row per application of the shared weights), the Mamba2
    state rides the slot-state pools;
  * whisper: each request may carry audio frame embeddings as its
    ``frontend`` — the encoder runs ONCE at admission and every decoder
    layer's cross K/V is written into the request's slot rows; text-only
    requests decode against zero cross K/V.

    PYTHONPATH=src python examples/serve_hybrid_archs.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import numpy as np
import jax

from repro.configs import ARCHS, reduce_for_smoke
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.serving import ContinuousBatchingEngine, Request, SamplingParams


def serve(arch_name, mesh, *, frontend_for=None):
    arch = reduce_for_smoke(ARCHS[arch_name])
    params = T.init_lm(jax.random.PRNGKey(0), arch)
    engine = ContinuousBatchingEngine(arch, params, mesh, slots=4,
                                      max_len=128, block_size=16,
                                      prefill_chunk=32)
    rng = np.random.default_rng(0)
    requests, has_fe = [], set()
    for i in range(8):
        prompt_len = int(rng.integers(8, 48))
        fe = None
        if frontend_for is not None and i % 2 == 0:   # every other request
            fe = rng.standard_normal(
                (1, arch.encoder.seq_len, arch.d_model)).astype(np.float32)
            has_fe.add(i)
        requests.append(Request(
            id=i,
            prompt=rng.integers(1, arch.vocab, size=prompt_len)
            .astype(np.int32),
            max_new_tokens=12, frontend=fe,
            # seeded sampling works on the slot-state archs too — the
            # sampler only sees logits, never the cache layout
            sampling=SamplingParams(temperature=0.7, top_p=0.9, seed=i)))
    outs = engine.generate(requests)
    s = engine.metrics.summary()
    print(f"[{arch.name}] {s['completed']} requests, {s['total_tokens']} "
          f"tokens ({s['decode_steps']} decode steps, "
          f"{s['prefill_chunks']} prefill chunks, occupancy "
          f"{s['slot_occupancy_mean']*100:.0f}%)")
    for o in outs[:2]:
        tag = " (audio frontend)" if o.request_id in has_fe else ""
        print(f"  req {o.request_id}{tag} [{o.finish_reason}]: {o.token_ids}")


def main():
    mesh = make_host_mesh()
    serve("zamba2-2.7b", mesh)
    serve("whisper-medium", mesh, frontend_for="audio")


if __name__ == "__main__":
    main()
