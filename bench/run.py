"""Run one cell of the benchmark and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the checkout's root.  With
``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result holds its
per-layer metrics.  Every run checks what the timed path produced against a
plain reference and prints each compared number beside its limit, last on
standard error and last in the result line.  The last line of standard
output is the result, one JSON object.

With no TPU, or fewer chips than the cell asks for, the run exits with a
code other than 0 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402

OUT = ROOT / ".bench_out"            # traces, removed once they are read


def device_info(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(f"this cell needs {chips} TPU chip(s); jax found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def per_layer(cell, root, out, trace, peaks) -> dict:
    """Each per-layer metric of the cell, read by bench/metrics/<name>.py.
    A reader that finds nothing to read returns None, and the metric is
    left out."""
    found = {}
    for m in cell.per_layer:
        mod = H.load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                            f"bench_metric_{m['name']}")
        v = mod.read(out["record"], trace, peaks)
        if v is not None:
            found[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return found


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = ROOT, require_chip: bool = True,
             t_start: float | None = None) -> dict:
    """One run; returns the result object (also what ``main`` prints)."""
    import jax

    from bench.peaks import PEAKS, peaks
    from repro.launch.compile_cache import enable_compile_cache

    cell = H.load_cell(workload, root)
    devices = device_info(cell.chips, require_chip)
    kind = devices[0].device_kind
    # an unknown device is an error; a CPU test run reads no device number
    chip = peaks(kind) if require_chip else PEAKS.get(kind, {})
    cache = None
    if require_chip:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cache = enable_compile_cache()
    H.log(f"{workload} seed {seed}: {len(devices)} x {kind}, compile cache "
          f"{cache}")
    # only a chip is traced: a CPU test run of --trace 1 reads the per-layer
    # metrics that need no trace
    trace_dir = OUT / f"trace-{workload}-{seed}" \
        if trace and require_chip else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    driver = H.load_module(root / "bench" / "drivers"
                           / f"{cell.traffic['driver']}.py",
                           f"bench_driver_{cell.traffic['driver']}")
    out = driver.run(H.Run(cell, seed, seconds, trace_dir,
                           T_START if t_start is None else t_start))
    gc.collect()
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        tr = None
        if trace_dir is not None:
            from bench import trace_reduce
            t = time.perf_counter()
            tr = trace_reduce.reduce(trace_dir)
            H.log(f"trace read in {time.perf_counter() - t:.1f}s")
            shutil.rmtree(trace_dir, ignore_errors=True)
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            H.log(f"trace: programs {json.dumps(tr['programs'])}")
        result["metrics"] = per_layer(cell, root, out, tr, chip)
        if tr is not None:
            result["breakdown"] = tr["breakdown"]
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in out["end_to_end"].items()
                             if k in units}
    checks = out["checks"]
    result["correct"] = bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks)
    result["device"] = device
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
