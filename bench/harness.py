"""What every driver shares: the cell's specification read from
``BENCHMARK.json`` and the files it names, the model built from its
configuration file, the host spans, and the result line.

A cell is found by name.  Its configuration is ``bench/configs/<config>.json``,
its traffic ``bench/traffic/<traffic>.json``, whose ``driver`` key names
``bench/drivers/<driver>.py``, and each per-layer metric is read by
``bench/metrics/<metric>.py``.  A later cell adds such files and edits none.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # metric entries of BENCHMARK.json for this cell
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with the files it names."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    file = {c["name"]: c["file"] for c in spec["configs"]}[w["config"]]
    config = json.loads((root / file).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, w["config"], w["traffic"], w["chips"], config, traffic,
                [m for m in spec["end_to_end"] if _reports(m, name)],
                [m for m in spec["per_layer"] if _reports(m, name)])


def load_module(path: pathlib.Path, name: str):
    """Import a file by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the model, from the configuration file
# ---------------------------------------------------------------------------

def arch_from_config(cfg: dict):
    """The program's ArchConfig for a configuration file: the registry's
    architecture at the file's depth, with every width the file states."""
    from repro.configs import cut_depth, get_arch

    base = get_arch(cfg["registry_arch"])
    dtype = cfg["torch_dtype"]
    if base.family == "ssm":
        n = cfg["n_layer"]
        arch = dataclasses.replace(
            cut_depth(base, n) if n != base.n_layers else base,
            d_model=cfg["d_model"], vocab=cfg["vocab_size"],
            tie_embeddings=cfg["tie_embeddings"],
            ssm=dataclasses.replace(base.ssm, d_state=cfg["d_state"],
                           head_dim=cfg["headdim"], expand=cfg["expand"],
                           n_groups=cfg["ngroups"], d_conv=cfg["d_conv"],
                           chunk=cfg["chunk_size"]))
    else:
        n = cfg["num_hidden_layers"]
        arch = dataclasses.replace(
            cut_depth(base, n) if n != base.n_layers else base,
            d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            tie_embeddings=cfg["tie_word_embeddings"])
    return dataclasses.replace(arch, dtype=dtype,
                      param_dtype=cfg.get("param_dtype", dtype))


# ---------------------------------------------------------------------------
# host spans: jax.profiler.TraceAnnotation, on the device trace's clock
# ---------------------------------------------------------------------------

def span(name: str, **kw):
    """A host span in the profiler's trace (a no-op when none is running)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


@contextlib.contextmanager
def traced(trace_dir):
    """Profile the device and the host spans into ``trace_dir`` (or do
    nothing when it is None)."""
    if trace_dir is None:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # spans only: no trace of every call
    opts.enable_hlo_proto = False
    opts.host_tracer_level = 1         # the benchmark's spans, little else
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        t = now()
        jax.profiler.stop_trace()
        log(f"trace written in {now() - t:.1f}s")


def now() -> float:
    return time.perf_counter()


class Phases:
    """Where set-up's time went: ``mark(name)`` closes the phase that ran
    since the last mark (the first since the process began)."""

    def __init__(self, t_start: float):
        self.last, self.parts = t_start, []

    def mark(self, name: str) -> None:
        t = now()
        self.parts.append((name, t - self.last))
        self.last = t

    def __str__(self) -> str:
        return ", ".join(f"{n} {s:.1f}s" for n, s in self.parts)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what a driver is given, and device memory
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    cell: Cell
    seed: int
    seconds: float
    trace_dir: "pathlib.Path | None"
    t_start: float                 # host clock when the process began


class Capture:
    """Wraps a jitted function; records the abstract arguments of its first
    call so that its compiled program's memory can be read later."""

    def __init__(self, fn):
        self.fn, self.args = fn, None

    def __call__(self, *args):
        if self.args is None:
            import jax
            self.args = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding)
                if hasattr(x, "sharding") else x, args)
        return self.fn(*args)

    def temp_bytes(self) -> int:
        return int(self.fn.lower(*self.args).compile()
                   .memory_analysis().temp_size_in_bytes)


def memory_peak_bytes(devices, programs) -> tuple[int, int]:
    """(the fullest device's peak as its allocator reports it, an estimate
    beside it: the arrays alive now plus the largest temporaries of the
    window's programs by ``compiled.memory_analysis()``).  Read after the
    window, before the reference runs.  A device whose allocator keeps no
    statistics (the CPU) reads 0."""
    import jax
    live = {d: 0 for d in devices}
    for a in jax.live_arrays():
        for sh in a.addressable_shards:
            if sh.device in live:
                live[sh.device] += sh.data.nbytes
    temp = max((p.temp_bytes() for p in programs), default=0)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(st.get("peak_bytes_in_use", 0) for st in stats)
    estimate = max(live.values()) + temp
    log(f"memory: allocator peak {peak} B; live arrays plus the largest "
        f"temporaries {estimate} B; allocator {json.dumps(stats[0])}")
    return int(peak), int(estimate)
