"""reprolint rule corpus + paged-cache sanitizer mutation tests.

Part 1 drives ``Linter.lint_sources`` with a minimal good/bad snippet per
rule: every rule must fire on its bad fixture and stay silent on the good
one (the false-positive half is as load-bearing as the detection half —
a noisy gate gets disabled).  Part 2 runs the real engine under the
sanitizer (clean under preemption + prefix sharing), then injects each
bug class the sanitizer exists to catch — leak, double-free, stale
incref, refcount/table mismatch, null-block write — and asserts the
report fires *with the allocation site* of the offending blocks.
Finally, the merged tree itself must lint clean: the CI gate in
executable form.
"""
from __future__ import annotations

import pathlib

import numpy as np
import pytest

from repro.analysis.lint import Linter, ModuleInfo, main as lint_main
from repro.analysis.sanitizer import CacheSanitizer, SanitizerError

REPO = pathlib.Path(__file__).resolve().parents[1]


def findings_for(path, src, rule=None):
    select = {rule} if rule else None
    return Linter(select=select).lint_sources({path: src})


def rules_hit(path, src, rule=None):
    return {f.rule for f in findings_for(path, src, rule)}


# ---------------------------------------------------------------------------
# rule corpus: one bad + one good snippet per rule
# ---------------------------------------------------------------------------

def test_jit_host_sync_bad_builder():
    src = """
def make_paged_decode_step(arch):
    def step(params, pools, tok):
        v = tok.sum()
        print(v)
        return v.item()
    return step
"""
    fs = findings_for("src/repro/runtime/bad.py", src, "jit-host-sync")
    assert len(fs) == 2
    assert any("print()" in f.message for f in fs)
    assert any(".item()" in f.message for f in fs)


def test_jit_host_sync_transitive_callee():
    """np.asarray in a helper the jitted step calls — the closure matters,
    not just the builder body."""
    src = """
import numpy as np

def helper(x):
    return np.asarray(x)

def make_paged_decode_step(arch):
    def step(params, tok):
        return helper(tok)
    return step
"""
    fs = findings_for("src/repro/runtime/bad.py", src, "jit-host-sync")
    assert len(fs) == 1
    assert "numpy.asarray" in fs[0].message
    assert "reached from a jitted scope" in fs[0].message


def test_jit_host_sync_good():
    src = """
import jax.numpy as jnp

def make_paged_decode_step(arch):
    def step(params, tok):
        return jnp.sum(tok)
    return step
"""
    assert not findings_for("src/repro/runtime/ok.py", src, "jit-host-sync")


def test_jit_recompile_hazard_bad_vs_shape_branch():
    """Branching on a traced value fires; branching on .shape (static
    under jit) must not — kernels/ops.py lives on that distinction."""
    src = """
def make_step(arch):
    def step(params, x):
        B, S = x.shape
        if S > 4:                 # static: fine
            x = x * 2
        if x.sum() > 0:           # traced: recompile/Concretization
            return x
        return -x
    return step
"""
    fs = findings_for("src/repro/runtime/bad.py", src,
                      "jit-recompile-hazard")
    assert len(fs) == 1
    assert fs[0].line == 7


def test_jit_recompile_hazard_respects_static_argnames():
    src = """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("causal",))
def f(q, causal):
    if causal:
        return q
    return -q
"""
    assert not findings_for("src/repro/kernels/ok.py", src,
                            "jit-recompile-hazard")


def test_jit_recompile_hazard_closure_params_are_static():
    """A make_* builder's own parameters are trace-time constants — the
    inner function may branch on them freely (make_train_step's
    microbatches switch)."""
    src = """
def make_train_step(arch, microbatches):
    def train_step(params, batch):
        if microbatches == 1:
            return batch
        return batch * 2
    return train_step
"""
    assert not findings_for("src/repro/runtime/ok.py", src,
                            "jit-recompile-hazard")


def test_prng_discipline_bad():
    src = """
import jax

def bad(seed, pos, vocab):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return jax.random.gumbel(k1, (vocab,))
"""
    fs = findings_for("src/repro/serving/bad.py", src, "prng-discipline")
    assert len(fs) == 2                       # the split AND the raw-key draw
    assert any("split" in f.message for f in fs)
    assert any("gumbel" in f.message for f in fs)


def test_prng_discipline_good_fold_in():
    src = """
import jax

def draw(seed, pos, vocab):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
    return jax.random.gumbel(key, (vocab,))
"""
    assert not findings_for("src/repro/serving/ok.py", src,
                            "prng-discipline")


def test_prng_discipline_scoped_to_serving():
    """split outside serving/ (e.g. training init) is legitimate."""
    src = """
import jax

def init(seed):
    return jax.random.split(jax.random.PRNGKey(seed))
"""
    assert not findings_for("src/repro/core/ok.py", src, "prng-discipline")


def test_refcount_pairing_leak_on_early_return():
    src = """
def leak(alloc, table, n):
    blocks = alloc.alloc(n)
    if blocks is None:
        return False
    if n > 4:
        return True
    table.extend(blocks)
    return True
"""
    fs = findings_for("src/repro/serving/bad.py", src, "refcount-pairing")
    assert len(fs) == 1
    assert "allocated line 3" in fs[0].message
    assert fs[0].line == 7                    # the leaking return


def test_refcount_pairing_exception_edge():
    """The ISSUE's exception-edge case: a call that can raise between
    alloc and ownership transfer, with no try protecting the blocks."""
    src = """
def edge(alloc, risky, n):
    blocks = alloc.alloc(n)
    if blocks is None:
        return None
    risky(n)
    return blocks
"""
    fs = findings_for("src/repro/serving/bad.py", src, "refcount-pairing")
    assert len(fs) == 1
    assert "exception edge" in fs[0].message


def test_refcount_pairing_discarded_result():
    src = """
def drop(alloc):
    alloc.alloc(1)
"""
    fs = findings_for("src/repro/serving/bad.py", src, "refcount-pairing")
    assert len(fs) == 1
    assert "discarded" in fs[0].message


def test_refcount_pairing_good_patterns():
    """The three sanctioned shapes: try/finally, immediate store (the
    real reserve()), and a decref loop."""
    src = """
def ok_finally(alloc, risky, n):
    blocks = alloc.alloc(n)
    if blocks is None:
        return None
    try:
        risky(n)
    finally:
        alloc.free(blocks)
    return True

def ok_store(self, rid, n):
    got = self.allocator.alloc(n)
    if got is None:
        return False
    self.tables.setdefault(rid, []).extend(got)
    return True

def ok_loop(alloc, n):
    blocks = alloc.alloc(n)
    if blocks is None:
        return
    for b in blocks:
        alloc.decref(b)
"""
    assert not findings_for("src/repro/serving/ok.py", src,
                            "refcount-pairing")


def test_atomic_write_bad():
    src = """
import json
import pathlib

def dump(path, data):
    with open(path, "w") as f:
        json.dump(data, f)
    pathlib.Path(path).write_text("x")
"""
    fs = findings_for("src/repro/serving/bad.py", src, "atomic-write")
    assert len(fs) == 2
    assert all("atomic_write_text" in f.message for f in fs)


def test_atomic_write_reads_are_fine():
    src = """
def load(path):
    with open(path) as f:
        return f.read()
"""
    assert not findings_for("src/repro/serving/ok.py", src, "atomic-write")


def test_clock_injection_bad():
    src = """
import time

def stamp():
    return time.time()
"""
    fs = findings_for("src/repro/serving/bad.py", src, "clock-injection")
    assert len(fs) == 1
    assert "time.time" in fs[0].message


def test_clock_injection_scoped_to_serving():
    src = """
import time

def stamp():
    return time.perf_counter()
"""
    assert not findings_for("src/repro/benchmarks_like/ok.py", src,
                            "clock-injection")


def test_inline_pragma_suppresses_exactly_that_rule():
    src = """
import time

def stamp():
    return time.perf_counter()  # reprolint: disable=clock-injection
"""
    assert not findings_for("src/repro/serving/ok.py", src)
    # a pragma for a different rule must NOT suppress
    src2 = src.replace("clock-injection", "atomic-write")
    assert rules_hit("src/repro/serving/bad.py", src2) == {"clock-injection"}


def test_module_info_serving_scope_detection():
    assert ModuleInfo("src/repro/serving/x.py", "").in_serving
    assert not ModuleInfo("src/repro/runtime/x.py", "").in_serving


def test_serving_scope_covers_cluster_subpackage():
    """The cluster subsystem sits under serving/, so every serving-scoped
    rule applies to it automatically — no per-rule path lists to keep in
    sync as the package grows."""
    assert ModuleInfo("src/repro/serving/cluster/router.py", "").in_serving
    assert ModuleInfo("src/repro/serving/cluster/worker.py", "").in_serving


def test_cluster_paths_hit_serving_scoped_rules():
    clocky = "import time\n\ndef f():\n    return time.monotonic()\n"
    assert rules_hit("src/repro/serving/cluster/router.py", clocky,
                     "clock-injection") == {"clock-injection"}
    asserty = "def f(x):\n    assert x, 'no'\n    return x\n"
    assert rules_hit("src/repro/serving/cluster/frontend.py", asserty,
                     "no-bare-assert") == {"no-bare-assert"}
    writey = ("def dump(path, text):\n"
              "    with open(path, 'w') as f:\n"
              "        f.write(text)\n")
    assert rules_hit("src/repro/serving/cluster/worker.py", writey,
                     "atomic-write") == {"atomic-write"}


def test_cluster_clock_pragma_suppresses_default_arg_line():
    """The Router takes ``clock=time.monotonic`` as an injectable default —
    the sanctioned pattern — and suppresses the banned-name finding with
    the per-line pragma, exactly as serving/metrics.py does."""
    src = ("import time\n\n\n"
           "class Router:\n"
           "    def __init__(self, handles, *,\n"
           "                 clock=time.monotonic):"
           "  # reprolint: disable=clock-injection\n"
           "        self._clock = clock\n")
    assert not findings_for("src/repro/serving/cluster/router.py", src,
                            "clock-injection")


def test_no_bare_assert_bad():
    src = """
def reserve(self, n):
    assert n >= 0, "negative reservation"
    return n
"""
    fs = findings_for("src/repro/serving/bad.py", src, "no-bare-assert")
    assert len(fs) == 1
    assert "python -O" in fs[0].message


def test_no_bare_assert_scoped_to_serving():
    src = "def f(x):\n    assert x\n    return x\n"
    assert not findings_for("src/repro/analysis/ok.py", src,
                            "no-bare-assert")
    assert not findings_for("tests/test_ok.py", src, "no-bare-assert")


def test_no_bare_assert_explicit_raise_is_clean():
    src = """
def reserve(self, n):
    if n < 0:
        raise ValueError("negative reservation")
    return n
"""
    assert not findings_for("src/repro/serving/ok.py", src,
                            "no-bare-assert")


# ---------------------------------------------------------------------------
# the gate itself: merged tree lints clean; CLI exit codes
# ---------------------------------------------------------------------------

def test_merged_tree_is_clean():
    """The CI gate in test form: the full lint target — src/repro plus
    the benchmarks/ and examples/ trees — has zero unsuppressed
    findings.  (benchmarks/examples joined the target when their serving
    drivers started holding BlockAllocator results and timestamps of
    their own; this test pins the wider scope so CI and local runs
    cannot silently diverge.)"""
    targets = [REPO / "src" / "repro", REPO / "benchmarks",
               REPO / "examples"]
    findings = Linter().lint_paths([str(t) for t in targets if t.exists()])
    assert findings == [], "\n".join(f.format() for f in findings)


def test_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "serving" / "bad.py"
    bad.parent.mkdir()
    bad.write_text("import time\n\ndef f():\n    return time.time()\n")
    assert lint_main([str(bad)]) == 1
    assert "clock-injection" in capsys.readouterr().out
    good = tmp_path / "serving" / "ok.py"
    good.write_text("def f():\n    return 1\n")
    assert lint_main([str(good)]) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_lists_all_seven_rules(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in ("jit-host-sync", "jit-recompile-hazard", "prng-discipline",
                 "refcount-pairing", "atomic-write", "clock-injection",
                 "no-bare-assert"):
        assert rule in out


# ---------------------------------------------------------------------------
# runtime sanitizer: clean runs, then one injection per bug class
# ---------------------------------------------------------------------------

jax = pytest.importorskip("jax")

from repro.launch.mesh import make_host_mesh        # noqa: E402
from repro.models import transformer as T           # noqa: E402
from repro.serving import (ContinuousBatchingEngine,  # noqa: E402
                           Request, SamplingParams)
from repro.serving.paged_cache import (NULL_BLOCK,  # noqa: E402
                                       PagedCacheConfig, PagedKVCache)
from serving_fixtures import TINY                   # noqa: E402

_PARAMS = {}


def _params():
    if "p" not in _PARAMS:
        _PARAMS["p"] = T.init_lm(jax.random.PRNGKey(0), TINY)
    return _PARAMS["p"]


def _engine(**kw):
    kw.setdefault("sanitizer", CacheSanitizer())
    return ContinuousBatchingEngine(
        TINY, _params(), make_host_mesh(), slots=kw.pop("slots", 2),
        max_len=kw.pop("max_len", 64), block_size=kw.pop("block_size", 4),
        prefill_chunk=kw.pop("prefill_chunk", 8), **kw)


def _reqs(n, plen=10, max_new=8, shared=0):
    common = np.arange(1, shared + 1, dtype=np.int32)
    return [Request(id=i,
                    prompt=np.concatenate(
                        [common, np.arange(40 + 5 * i, 40 + 5 * i + plen,
                                           dtype=np.int32) % 250 + 1]),
                    max_new_tokens=max_new,
                    sampling=SamplingParams(temperature=0.7, seed=i))
            for i in range(n)]


def _cache():
    return PagedKVCache(TINY, PagedCacheConfig(
        block_size=4, num_blocks=10, max_blocks_per_seq=8))


def test_sanitizer_clean_under_preemption_and_sharing():
    """The hardest legitimate path — prefix sharing, LRU retirement and
    recompute-preemption under a tight pool — must produce ZERO reports:
    a sanitizer that cries wolf on correct code is worse than none."""
    eng = _engine(slots=3, num_blocks=13, share_prefix=True)
    outs = eng.generate(_reqs(8, shared=12, max_new=10))
    assert len(outs) == 8
    assert eng.metrics.preemptions > 0, \
        "pool not tight enough — preemption path went unexercised"
    rep = eng.sanitizer.report()
    assert rep["violations"] == 0
    assert rep["step_checks"] > 0 and rep["allocs"] > 0


def test_sanitizer_env_var_auto_attach(monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    eng = _engine(sanitizer=None)
    assert isinstance(eng.sanitizer, CacheSanitizer)
    assert eng.cache.allocator.observer is eng.sanitizer
    monkeypatch.delenv("REPRO_SANITIZE")
    assert _engine(sanitizer=None).sanitizer is None


def test_sanitizer_detects_double_free_with_sites():
    cache = _cache()
    san = CacheSanitizer().attach(cache)
    assert cache.reserve(0, 8)
    victim = cache.tables[0][0]
    cache.release(0)
    with pytest.raises(SanitizerError) as e:
        cache.allocator.decref(victim)
    msg = str(e.value)
    assert "double free" in msg
    # the report must carry backtraces: where the block was allocated
    # (the reserve above) and where it was first freed (the release)
    assert "allocated at" in msg and "previously freed at" in msg
    assert "test_analysis.py" in msg
    assert san.counters["violations"] == 1


def test_sanitizer_detects_stale_incref():
    cache = _cache()
    CacheSanitizer().attach(cache)
    assert cache.reserve(0, 8)
    stale = cache.tables[0][-1]
    cache.release(0)
    with pytest.raises(SanitizerError, match="stale reference"):
        cache.allocator.incref(stale)
    with pytest.raises(SanitizerError, match="null block"):
        cache.allocator.incref(NULL_BLOCK)


def test_sanitizer_detects_refcount_table_mismatch():
    """A reference the ground truth can't account for — e.g. an incref
    with no table or index holding the block — must be caught at the next
    step check, with the allocation site."""
    cache = _cache()
    san = CacheSanitizer().attach(cache)
    assert cache.reserve(0, 8)
    cache.allocator.incref(cache.tables[0][0])     # stranded reference
    with pytest.raises(SanitizerError) as e:
        san.check_cache()
    assert "refcount mismatch" in str(e.value)
    assert "allocated at" in str(e.value)


def test_sanitizer_detects_lost_table_reference():
    """The dual: a block dropped from a table while the allocator still
    counts its reference (the lost-ref flavor of the same class)."""
    cache = _cache()
    san = CacheSanitizer().attach(cache)
    assert cache.reserve(0, 8)
    cache.tables[0].pop()                          # ref lost, count kept
    with pytest.raises(SanitizerError, match="refcount mismatch"):
        san.check_cache()


def test_sanitizer_detects_null_block_write():
    """A slot position past its table's capacity means the next device
    write scatters into reserved block 0."""
    eng = _engine()
    eng.submit(_reqs(1)[0])
    while not any(s.busy for s in eng.slots):
        eng.step()
    slot = next(s for s in eng.slots if s.busy)
    table = eng.cache.tables[slot.req.id]
    slot.pos = len(table) * eng.cache.cfg.block_size + 1
    with pytest.raises(SanitizerError, match="null-block write"):
        eng.sanitizer.check_engine_step(eng)


def test_sanitizer_detects_leak_at_drain():
    """Blocks allocated but owned by nobody once the engine drains — the
    report names the allocation site of every leaked block."""
    eng = _engine()
    eng.generate(_reqs(2))                         # clean drain (checked)
    leaked = eng.cache.allocator.alloc(2)          # stray grant, never freed
    assert leaked is not None
    with pytest.raises(SanitizerError) as e:
        eng.sanitizer.check_drained(eng)
    msg = str(e.value)
    assert "leaked block" in msg
    assert "allocated at" in msg and "test_analysis.py" in msg


def test_sanitizer_zero_cost_when_detached(monkeypatch):
    """Production path: no observer, no sanitizer attribute cost beyond
    one None check — and crucially no behavior change.  REPRO_SANITIZE
    must be cleared: the whole suite also runs under REPRO_SANITIZE=1 in
    CI, which would auto-attach to the engine this test needs bare."""
    monkeypatch.delenv("REPRO_SANITIZE", raising=False)
    eng = _engine(sanitizer=None)
    assert eng.sanitizer is None
    assert eng.cache.allocator.observer is None
    outs = eng.generate(_reqs(2))
    assert [o.request_id for o in outs] == [0, 1]
