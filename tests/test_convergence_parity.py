"""Paper claim (Fig 4 / ±0.5%): parallelism strategies do not change model
quality.  In GSPMD terms: sharded and unsharded training are the SAME math —
validated by running identical steps on a 1-device mesh vs an 8-virtual-
device mesh (DP and TP shardings) in a subprocess and comparing losses."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs.base import ArchConfig, Segment
from repro.models import transformer as T
from repro.optim import optimizers as O
from repro.runtime import steps as ST
from repro.data import SyntheticLM

arch = ArchConfig(name="t", family="dense", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=4, d_ff=128, vocab=256,
                  pattern=(Segment(("attn",), 2),), dtype="float32",
                  param_dtype="float32")

def run(mesh_shape, axes, tok_spec, w_col, w_row):
    mesh = jax.make_mesh(mesh_shape, axes, (AxisType.Auto,) * len(axes))
    opt = O.adamw(1e-3)
    step = ST.make_train_step(arch, opt)
    params = T.init_lm(jax.random.PRNGKey(0), arch)
    ostate = opt[0](params)

    def spec_of(path, leaf):
        name = path[-2].key if hasattr(path[-2], "key") else ""
        if leaf.ndim >= 2 and name in ("wq", "wk", "wv", "w_in", "w_gate"):
            return NamedSharding(mesh, P(*([None]*(leaf.ndim-1) + [w_col])))
        if leaf.ndim >= 2 and name in ("wo", "w_out"):
            return NamedSharding(mesh, P(*([None]*(leaf.ndim-2) + [w_row, None])))
        return NamedSharding(mesh, P())
    pspecs = jax.tree_util.tree_map_with_path(spec_of, params)
    params = jax.device_put(params, pspecs)
    ostate = jax.device_put(ostate, jax.tree.map(
        lambda _: NamedSharding(mesh, P()), ostate))
    data = SyntheticLM(arch.vocab, 32, 8, seed=3)
    losses = []
    jstep = jax.jit(step)
    for _ in range(8):
        b = next(data)
        batch = {k: jax.device_put(jnp.asarray(v),
                                   NamedSharding(mesh, P(tok_spec, None)))
                 for k, v in b.items()}
        params, ostate, m = jstep(params, ostate, batch)
        losses.append(float(m["ce"]))
    return losses

single = run((1, 1), ("data", "model"), None, None, None)
dp = run((8, 1), ("data", "model"), "data", None, None)
tp = run((1, 8), ("data", "model"), None, "model", "model")
print(json.dumps({"single": single, "dp": dp, "tp": tp}))
"""


@pytest.mark.slow
def test_sharded_training_is_same_math():
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"      # the 8 virtual devices are CPU ones
    out = subprocess.run([sys.executable, "-c", SCRIPT, src],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    single, dp, tp = res["single"], res["dp"], res["tp"]
    np.testing.assert_allclose(single, dp, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(single, tp, rtol=2e-3, atol=2e-3)
