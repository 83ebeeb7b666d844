"""Trainer / serving engine / monitor runtime tests (single-device CPU
mesh)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, Segment, ShapeSpec
from repro.core.profiler import StepMonitor
from repro.data import SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.runtime.trainer import TrainConfig, Trainer
from repro.serving import ContinuousBatchingEngine, Request

TINY = ArchConfig(name="tiny-rt", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                  pattern=(Segment(("attn",), 2),), dtype="float32",
                  param_dtype="float32")
SHAPE = ShapeSpec("smoke", 32, 8, "train")


def test_trainer_end_to_end(tmp_path):
    mesh = make_host_mesh()
    tr = Trainer(TINY, SHAPE, mesh,
                 TrainConfig(lr=3e-3, warmup_steps=2, total_steps=40,
                             checkpoint_every=10),
                 checkpoint_dir=str(tmp_path / "ck"))
    params, opt_state = tr.init_state()
    data = SyntheticLM(TINY.vocab, 32, 8)
    params, opt_state, hist = tr.train(params, opt_state, data, steps=20)
    assert len(hist) == 20
    assert hist[-1]["loss"] < hist[0]["loss"]
    tr.ckpt.wait()
    assert tr.ckpt.latest_step() == 20


def test_trainer_restart_resumes(tmp_path):
    mesh = make_host_mesh()
    cfg = TrainConfig(lr=1e-3, checkpoint_every=5, total_steps=40)
    tr = Trainer(TINY, SHAPE, mesh, cfg, checkpoint_dir=str(tmp_path / "ck"))
    params, opt_state = tr.init_state()
    data = SyntheticLM(TINY.vocab, 32, 8)
    params, opt_state, _ = tr.train(params, opt_state, data, steps=10)
    tr.ckpt.wait()

    tr2 = Trainer(TINY, SHAPE, mesh, cfg, checkpoint_dir=str(tmp_path / "ck"))
    p2, o2 = tr2.init_state()
    p2, o2 = tr2.maybe_restore(p2, o2)
    assert tr2.step == 10
    a = np.concatenate([np.ravel(x) for x in jax.tree.leaves(params)])
    b = np.concatenate([np.ravel(x) for x in jax.tree.leaves(p2)])
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_step_monitor_triggers_on_drift():
    mon = StepMonitor(alpha=0.5, drift_threshold=0.2, min_steps=5)
    for _ in range(10):
        assert not mon.update(1.0)
    fired = any(mon.update(3.0) for _ in range(10))
    assert fired


def test_server_greedy_decode_matches_reference():
    mesh = make_host_mesh()
    params = T.init_lm(jax.random.PRNGKey(0), TINY)
    eng = ContinuousBatchingEngine(TINY, params, mesh, slots=2, max_len=64)
    prompts = [np.arange(1, 9, dtype=np.int32) + i for i in range(3)]
    for i, p in enumerate(prompts):
        eng.submit(Request(id=i, prompt=p, max_new_tokens=4))
    eng.run_until_drained()
    assert len(eng.completed) == 3
    # reference greedy decode with plain forward passes
    for out in eng.completed:
        assert len(out.token_ids) == 4
        ctx = list(prompts[out.request_id])
        for tok in out.token_ids:
            logits = T.lm_apply(params, TINY,
                                jnp.asarray([ctx], jnp.int32)).logits
            expect = int(jnp.argmax(logits[0, -1, : TINY.vocab]))
            assert tok == expect, (out.request_id, ctx, tok, expect)
            ctx.append(tok)
