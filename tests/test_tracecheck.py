"""tracecheck (IR-level serving-step analysis) tests.

Three layers, mirroring tests/test_analysis.py for reprolint:

  * positive: the analyzers are clean over reference registry archs, and a
    real engine stays within the per-step compile budgets for every tiny
    serving family (the runtime recompile regression the trace-cache
    analyzer models statically);
  * mutation-injection: each of the five analyzers provably FIRES when its
    invariant is broken (un-donated cache, injected host callback, extra
    host-bound output, perturbed sharding declarations, zeroed cost
    tolerance, engine shape leak);
  * contracts: BENCH_static_costs.json schema validation, costmodel
    serving predictions against the committed bench rows, and the shared
    reprolint/tracecheck finding emitters (json / github formats).
"""
from __future__ import annotations

import io
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.analysis import ircost as IC
from repro.analysis import tracecheck as TC
from repro.analysis.lint import Finding, emit_findings
from repro.core import costmodel as CM
from repro.runtime import steps as ST
from serving_fixtures import ARCH_BY_KEY

BENCH_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_static_costs.json"

# small geometry: every lowering in this file compiles in seconds
GEOM = IC.ServeGeom(slots=2, max_len=32, block_size=8, prefill_chunk=8)
MESH = TC.serve_mesh()

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the serving CI mesh) to distinguish shardings")


def _ctx(arch_or_name) -> TC.ArchContext:
    if isinstance(arch_or_name, str):
        return TC.ArchContext.for_arch(arch_or_name, GEOM, MESH)
    return TC.ArchContext(arch_or_name, GEOM, MESH)


# ---------------------------------------------------------------------------
# positive: clean over reference archs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["qwen3-8b", "mamba2-780m"])
def test_static_analyzers_clean_on_reference_archs(name):
    findings = TC.run_analyzers(
        [name], select=["donation", "host-transfer", "sharding",
                        "cost-drift"], geom=GEOM, mesh=MESH)
    assert findings == [], [f.format() for f in findings]


@pytest.mark.slow
def test_trace_cache_clean_on_tiny_arch():
    assert TC.check_trace_cache(_ctx(ARCH_BY_KEY["tiny"])) == []


# ---------------------------------------------------------------------------
# satellite: engine recompile regression — every tiny serving family stays
# within the tracecheck budgets over a drained mixed workload
# ---------------------------------------------------------------------------

def _drained_engine(arch):
    from repro.serving.engine import ContinuousBatchingEngine
    params = jax.jit(lambda k: IC.T.init_lm(k, arch))(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(arch, params, MESH, slots=2, max_len=48,
                                   block_size=4, num_blocks=13,
                                   prefill_chunk=8)
    eng.generate(TC._mixed_workload(_ctx(arch)))
    return eng


def _assert_within_budget(eng):
    jitted = {"paged_prefill": eng._prefill, "paged_decode": eng._decode}
    if eng._admit_slot_state is not None:
        jitted["slot_admit"] = eng._admit_slot_state
    for kind, fn in jitted.items():
        n = fn._cache_size()
        assert 1 <= n <= TC.TRACE_BUDGETS[kind], \
            f"{eng.arch.name}/{kind}: {n} trace signatures " \
            f"(budget {TC.TRACE_BUDGETS[kind]})"


@pytest.mark.parametrize("key", ["tiny", "hybrid", "mla"])
def test_engine_recompile_budget(key):
    _assert_within_budget(_drained_engine(ARCH_BY_KEY[key]))


@pytest.mark.slow
@pytest.mark.parametrize("key", ["ssm", "cross", "shared", "encdec"])
def test_engine_recompile_budget_all_families(key):
    _assert_within_budget(_drained_engine(ARCH_BY_KEY[key]))


# ---------------------------------------------------------------------------
# mutation-injection: every analyzer fires on its broken invariant
# ---------------------------------------------------------------------------

def _mutated(ctx, kind, fn=None, jit_kwargs=None, lower=True):
    """A LoweredStep whose jit deviates from the engine's construction."""
    real = fn or IC.build_step_fn(ctx.arch, kind)
    args = IC.step_arguments(ctx.arch, kind, ctx.geom)
    lowered = jax.jit(real, **(jit_kwargs or {})).lower(*args) if lower \
        else None
    return IC.LoweredStep(ctx.arch, kind, real, args, lowered)


def test_donation_analyzer_fires_on_undonated_cache():
    ctx = _ctx("qwen3-8b")
    bad = _mutated(ctx, "paged_decode")          # plain jit: donates nothing
    ctx.lowered = lambda kind, *, meshful: bad
    findings = TC.check_donation(ctx)
    assert any(f.rule == "donation" and "STEP_DONATION" in f.message
               for f in findings), [f.format() for f in findings]


def test_host_transfer_analyzer_fires_on_injected_callback():
    ctx = _ctx("qwen3-8b")
    real = IC.build_step_fn(ctx.arch, "paged_decode")

    def leaky(*args):
        out = real(*args)
        jax.debug.callback(lambda t: None, out[0])   # host round-trip
        return out

    bad = _mutated(ctx, "paged_decode", fn=leaky, lower=False)
    ctx.lowered = lambda kind, *, meshful: bad
    findings = TC.check_host_transfer(ctx)
    assert any(f.rule == "host-transfer" and "callback" in f.message
               for f in findings), [f.format() for f in findings]


def test_host_transfer_analyzer_fires_on_extra_output():
    ctx = _ctx("qwen3-8b")
    real = IC.build_step_fn(ctx.arch, "paged_decode")

    def chatty(*args):
        tok, logp, cache = real(*args)
        return tok, logp, cache, args[0]         # leaks params to host

    bad = _mutated(ctx, "paged_decode", fn=chatty, lower=False)
    ctx.lowered = lambda kind, *, meshful: bad
    findings = TC.check_host_transfer(ctx)
    assert any("sanctioned" in f.message for f in findings), \
        [f.format() for f in findings]


def test_sharding_analyzer_fires_on_spec_tree_drift():
    ctx = _ctx("qwen3-8b")

    class _Plan:
        def __init__(self, real):
            self._real = real

        def __getattr__(self, name):
            return getattr(self._real, name)

        def paged_cache_specs(self):
            specs = self._real.paged_cache_specs()
            mutated = [dict(seg) for seg in specs]
            first = next(iter(mutated[0]))
            mutated[0][first] = {"k": mutated[0][first]["k"]}   # drop "v"
            return mutated

    ctx._plan = _Plan(ctx.plan)
    findings = TC.check_sharding(ctx)
    assert any(f.rule == "sharding" for f in findings), \
        [f.format() for f in findings]


@multi_device
def test_sharding_analyzer_fires_on_replicated_pool():
    ctx = _ctx("qwen3-8b")
    from jax.sharding import PartitionSpec as P

    class _Plan:
        def __init__(self, real):
            self._real = real

        def __getattr__(self, name):
            return getattr(self._real, name)

        def paged_cache_specs(self):
            # declare every pool model-replicated: the compiled steps
            # (lowered against the REAL plan) shard kv heads over `model`,
            # so conformance must fail
            return jax.tree.map(lambda s: P(),
                                self._real.paged_cache_specs())

    real_plan = ctx.plan
    for kind in ctx.kinds():                      # lower with the real plan
        ctx.lowered(kind, meshful=True)
    ctx._plan = _Plan(real_plan)
    findings = TC.check_sharding(ctx)
    assert any(f.rule == "sharding" and "declares" in f.message
               for f in findings), [f.format() for f in findings]


def test_cost_drift_analyzer_fires_on_zero_tolerance(monkeypatch):
    ctx = _ctx("qwen3-8b")
    monkeypatch.setattr(CM, "SERVING_FLOPS_RTOL", 0.0)
    monkeypatch.setattr(CM, "SERVING_BYTES_RFACTOR", 1.0)
    findings = TC.check_cost_drift(ctx)
    assert any(f.rule == "cost-drift" for f in findings), \
        "XLA and the analytic model can never agree to 0 ULP — a zeroed " \
        "tolerance must fire"


@pytest.mark.slow
def test_trace_cache_analyzer_fires_on_shape_leak(monkeypatch):
    from repro.serving.engine import ContinuousBatchingEngine as CBE

    orig = CBE._prefill_chunk

    def leaky(self):
        ran = orig(self)
        if ran and not getattr(self, "_leaked", False):
            # one extra prefill at HALF the chunk width: the class of bug
            # where a caller stops padding and every distinct prompt tail
            # compiles its own executable
            self._leaked = True
            mbps = self.cache.cfg.max_blocks_per_seq
            _, _, self.cache.pools = self._prefill(
                self.params, self.cache.pools,
                jnp.zeros((1, self.prefill_chunk // 2), jnp.int32),
                jnp.zeros((1,), jnp.int32),
                jnp.zeros((1, mbps), jnp.int32),
                jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
                jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
                jnp.ones((1,), jnp.float32), jnp.zeros((1,), jnp.uint32))
        return ran

    monkeypatch.setattr(CBE, "_prefill_chunk", leaky)
    findings = TC.check_trace_cache(_ctx(ARCH_BY_KEY["tiny"]))
    assert any(f.rule == "trace-cache" and "trace signatures" in f.message
               for f in findings), [f.format() for f in findings]


# ---------------------------------------------------------------------------
# donation convention (satellite: one helper, no per-call-site tables)
# ---------------------------------------------------------------------------

def test_jit_step_owns_donation():
    with pytest.raises(ValueError, match="jit_step owns donate_argnums"):
        ST.jit_step("paged_decode", lambda p, c: (p, c),
                    donate_argnums=(0,))


def test_step_donation_covers_every_kind():
    assert set(ST.STEP_DONATION) == {"train", "paged_prefill",
                                     "paged_decode", "slot_admit"}
    # params are never donated outside training
    for kind, argnums in ST.STEP_DONATION.items():
        if kind != "train":
            assert argnums == (1,), (kind, argnums)


# ---------------------------------------------------------------------------
# BENCH_static_costs.json: schema + costmodel cross-validation (satellite)
# ---------------------------------------------------------------------------

def _bench_doc():
    with open(BENCH_PATH) as f:
        return json.load(f)


def test_committed_bench_is_valid():
    errors = TC.validate_bench(_bench_doc())
    assert errors == []


def test_validate_bench_catches_corruption():
    doc = _bench_doc()
    assert TC.validate_bench({"rows": []})   # missing top-level keys
    broken = json.loads(json.dumps(doc))
    broken["rows"][0]["flops_rel_err"] = 9.9
    assert any("exceeds" in e for e in TC.validate_bench(broken))
    short = json.loads(json.dumps(doc))
    dropped = short["rows"].pop()
    assert any(dropped["arch"] in e for e in TC.validate_bench(short))


@pytest.mark.parametrize("name", ["qwen3-8b", "mamba2-780m"])
def test_costmodel_serving_predictions_match_bench(name):
    """core/costmodel.predict_serving_step must reproduce the committed
    predicted values exactly AND stay within the declared tolerance of the
    committed extracted FLOPs — the cost model is a checked serving input,
    not a free-floating estimate."""
    doc = _bench_doc()
    rows = {(r["arch"], r["step"]): r for r in doc["rows"]}
    arch = configs.reduce_for_smoke(configs.get_arch(name))
    for step in ("paged_prefill", "paged_decode"):
        row = rows[(arch.name, step)]
        pred = CM.predict_serving_step(
            arch, batch=row["batch"], new_tokens=row["new_tokens"],
            table_len=row["table_len"])
        assert pred["flops"] == pytest.approx(row["flops_predicted"],
                                              rel=1e-9)
        # same normalization as tracecheck.bench_row: drift relative to
        # the model's prediction
        rel = abs(pred["flops"] - row["flops_extracted"]) / \
            max(pred["flops"], 1.0)
        assert rel <= doc["tolerances"]["flops_rtol"], \
            f"{arch.name}/{step}: rel err {rel:.3f}"


# ---------------------------------------------------------------------------
# finding emitters (shared reprolint/tracecheck output formats)
# ---------------------------------------------------------------------------

_FINDINGS = [Finding("src/x.py", 3, 1, "clock-injection", "bad\nclock"),
             Finding("qwen3-8b-smoke/paged_decode", 0, 0, "donation",
                     "cache 50% undonated")]


def test_emit_findings_json_round_trips():
    buf = io.StringIO()
    emit_findings(_FINDINGS, "json", stream=buf)
    parsed = json.loads(buf.getvalue())
    assert [p["rule"] for p in parsed] == ["clock-injection", "donation"]
    assert parsed[0]["line"] == 3 and parsed[1]["path"].endswith("decode")


def test_emit_findings_github_annotations():
    buf = io.StringIO()
    emit_findings(_FINDINGS, "github", tool="tracecheck", stream=buf)
    lines = buf.getvalue().splitlines()
    assert lines[0].startswith("::error file=src/x.py,line=3,col=1,"
                               "title=tracecheck(clock-injection)::")
    assert "%0A" in lines[0] and "\n" not in lines[0][2:]
    assert "title=tracecheck(donation)" in lines[1]


def test_lint_cli_format_json(tmp_path, capsys):
    from repro.analysis.lint import main as lint_main
    (tmp_path / "serving").mkdir()               # clock-injection is scoped
    bad = tmp_path / "serving" / "bad.py"
    bad.write_text("import time\n\n"
                   "def submit(self, req):\n"
                   "    t = time.perf_counter()\n"
                   "    return t\n")
    rc = lint_main([str(bad), "--select", "clock-injection",
                    "--format", "json"])
    out = capsys.readouterr().out
    parsed = json.loads(out)                     # whole stdout is JSON
    assert rc == 1 and parsed \
        and parsed[0]["rule"] == "clock-injection"


def test_tracecheck_cli_plumbing(tmp_path, capsys):
    assert TC.main(["--list-analyzers"]) == 0
    out = capsys.readouterr().out
    for name in TC.ANALYZERS:
        assert name in out
    with pytest.raises(SystemExit):
        TC.main(["--select", "nope"])
    bench = tmp_path / "bench.json"
    bench.write_text(json.dumps({"rows": []}))
    assert TC.main(["--validate-bench", str(bench)]) == 1
    bench.write_text(BENCH_PATH.read_text())
    assert TC.main(["--validate-bench", str(bench)]) == 0
