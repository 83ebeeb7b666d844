"""The control and the planted faults, at a cell's own size, on the chip.

    python bench/control.py --workload <name> --seeds 1,2,3 [--seconds 40]

Training cells: for each seed, the reference's readings in float32 against
(a) the control, the reference computed in float8, and (b) the reference
with a fault planted: the mean taken over half the batch, and on more than
one chip the first chip's rows alone (the exchange between chips left
out).  No program runs; a state left unchanged reads 1 by construction.

Serving cells: for each seed, one run of the cell (window, drain and
check), and beside the program's widest gap the control's: at each
position of the same prompts and served tokens, the gap of the token that
the float8 reference ranks first.

Each reading is printed as a JSON line; the benchmark's own runs do not run
this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def train_control(cell, seeds):
    import jax

    from bench.drivers import train as D
    from bench.harness import arch_from_config
    from repro.models import transformer as T

    arch = arch_from_config(cell.config)
    rows = cell.traffic["batch_per_chip"] * cell.chips
    abstract = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), arch))
    devices = jax.devices()[:cell.chips]
    variants = {"control_float8": {"lowp": True},
                "fault_half_batch": {"grad_rows": rows // 2}}
    if cell.chips > 1:
        variants["fault_no_exchange"] = {"grad_rows": rows // cell.chips}
    for seed in seeds:
        want = D.reference_readings(cell.config, cell.traffic, seed, rows,
                                    abstract, devices)
        for name, kw in variants.items():
            got = D.reference_readings(cell.config, cell.traffic, seed, rows,
                                       abstract, devices, **kw)
            print(json.dumps({"cell": cell.name, "seed": seed,
                              "reading": name,
                              "checks": D.checks(cell.config, got, want)}),
                  flush=True)


def serve_control(cell, seeds, seconds):
    from bench import harness as H
    from bench.drivers import serve as D

    real = D.reference_checks

    def both(cfg, abstract, seed, arrivals, finished):
        own = real(cfg, abstract, seed, arrivals, finished)
        ctl = real(cfg, abstract, seed, arrivals, finished, control=True)
        return own + [dict(c, name="control_" + c["name"]) for c in ctl]
    D.reference_checks = both
    for seed in seeds:
        out = D.run(H.Run(cell, seed, seconds, None, time.perf_counter()))
        print(json.dumps({"cell": cell.name, "seed": seed,
                          "checks": out["checks"]}), flush=True)


def main(argv=None) -> int:
    from bench.harness import load_cell
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    a = ap.parse_args(argv)
    enable_compile_cache()
    cell = load_cell(a.workload)
    seeds = [int(s) for s in a.seeds.split(",")]
    if cell.traffic["driver"] == "train":
        train_control(cell, seeds)
    else:
        serve_control(cell, seeds, a.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
