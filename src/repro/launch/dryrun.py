import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell: build the ASA plan,
lower + compile the real step function against ShapeDtypeStruct stand-ins
(no allocation), print memory_analysis / cost_analysis, and parse the
collective schedule out of the partitioned HLO for §Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.

The two lines above the docstring request 512 placeholder devices BEFORE
jax initializes (jax locks the device count on first init; consequently no
`from __future__ import annotations` in this module).
"""
import argparse
import dataclasses
import json
import pathlib
import re
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import ircost as IR
from repro.configs import ARCHS, SHAPES, get_arch, shape_applicable
from repro.core import components as C
from repro.core import sharding as SH
from repro.core.asa import AdaptiveScheduler
from repro.launch.mesh import make_production_mesh, mesh_shape_of
from repro.optim import optimizers as O
from repro.runtime import steps as ST

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# archs whose optimizer states only fit with int8 moments (DESIGN.md §7)
QUANTIZED_OPT = {"arctic-480b", "deepseek-v3-671b"}

# serving cells' page and prompt-chunk sizes (the chat cell's engine)
SERVE_BLOCK, SERVE_CHUNK = 16, 256

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _bytes_of_shape_str(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s(\w[\w\-]*)\(")
_CALLEE_RE = re.compile(r"(?:body|to_apply|branch_computations|called_computations)="
                        r"\{?%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")


def _split_computations(hlo_text: str) -> dict[str, list[str]]:
    """Split the HLO module into computations.  Headers look like
    `%region_0.123 (arg: (s32[], ...)) -> (...) {` — names captured up to the
    first '(' (arg types may contain nested parens)."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if cur is None:
            m = _COMP_HDR.match(stripped)
            if m and stripped.endswith("{") and not line.startswith("  "):
                cur = m.group(1)
                comps[cur] = []
        else:
            comps[cur].append(line)
            if stripped == "}":
                cur = None
    return comps


def _trip_count(cond_lines: list[str]) -> int:
    """Heuristic: the loop bound is the largest integer constant compared
    against in the condition computation."""
    best = 1
    for line in cond_lines:
        if "compare" in line or "constant" in line:
            for c in _CONST_RE.findall(line):
                best = max(best, int(c))
    return best


def parse_collectives(hlo_text: str) -> dict:
    """Trip-count-aware collective accounting: result bytes of every
    collective op, scaled by the product of enclosing while-loop trip counts
    (scan bodies appear once in HLO but execute trip times).  Per-device
    traffic; x chips = fabric-total."""
    comps = _split_computations(hlo_text)

    import functools as _ft

    @_ft.lru_cache(maxsize=None)
    def totals(comp_name: str) -> tuple:
        acc = {k: [0, 0] for k in _COLLECTIVES}
        for line in comps.get(comp_name, ()):
            m = _OP_RE.match(line)
            if not m:
                continue
            shp, opname = m.group(2), m.group(3)
            matched = False
            for coll in _COLLECTIVES:
                if opname.replace("_", "-").startswith(coll):
                    acc[coll][0] += _bytes_of_shape_str(shp)
                    acc[coll][1] += 1
                    matched = True
                    break
            if matched:
                continue
            if opname == "while":
                bm = _CALLEE_RE.search(line)
                cm = _COND_RE.search(line)
                if bm:
                    trips = _trip_count(comps.get(cm.group(1), [])) if cm else 1
                    sub = totals(bm.group(1))
                    for k, (b, c) in zip(_COLLECTIVES, sub):
                        acc[k][0] += trips * b
                        acc[k][1] += trips * c
            else:
                for callee in _CALLEE_RE.findall(line):
                    if callee in comps:
                        sub = totals(callee)
                        for k, (b, c) in zip(_COLLECTIVES, sub):
                            acc[k][0] += b
                            acc[k][1] += c
        return tuple((acc[k][0], acc[k][1]) for k in _COLLECTIVES)

    entry = None
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY\s+%?([\w.\-]+)", line)
            if m:
                entry = m.group(1)
    if entry is None or entry not in comps:
        entry = max(comps, key=lambda c: len(comps[c])) if comps else None
    out = {}
    res = totals(entry) if entry else tuple((0, 0) for _ in _COLLECTIVES)
    for k, (b, c) in zip(_COLLECTIVES, res):
        out[k] = {"bytes": int(b), "count": int(c)}
    out["total_bytes"] = int(sum(b for b, _ in res))
    return out


def _sds(tree, specs, mesh):
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    return jax.tree.map(
        lambda leaf, s: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=NamedSharding(mesh, s)),
        tree, specs)


def build_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
               faithful: bool = False, remat: Optional[str] = None,
               seq_shard: bool = False, opt8bit: bool = False,
               moe_ep: bool = False):
    """Construct (fn, args_sds, plan, meta) for one dry-run cell.

    seq_shard=True turns on Megatron-style sequence parallelism: layer
    boundary activations sharded over `model` on the sequence axis (§Perf).
    opt8bit=True forces int8 optimizer moments (halves state memory — opens
    uniform-DP plans for small models, §Perf).
    """
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    ms = mesh_shape_of(mesh)
    opt_preset = ("adamw8bit" if (arch_name in QUANTIZED_OPT or opt8bit)
                  else "adamw32")
    # seq-sharding the scan carry breaks on SSM families (the conv/scan mix
    # tokens across shard boundaries -> GSPMD gathers); keep batch-only there
    seq_ok = seq_shard and arch.family not in ("ssm", "hybrid") \
        and shape.kind == "train" and shape.seq_len % ms.model == 0
    from repro.core import sharding as SHmod
    from repro.models import moe as moe_mod
    if moe_ep and arch.moe is not None:
        SHmod.MOE_EP_AXIS = "data"
        moe_mod.EP_CONSTRAINTS = ("data", "model",
                                  SH.batch_axes(ms, shape.global_batch))
    else:
        SHmod.MOE_EP_AXIS = "model"
        moe_mod.EP_CONSTRAINTS = None

    sched = AdaptiveScheduler(faithful=faithful, opt_preset=opt_preset,
                              remat="full", seq_sharded=seq_ok,
                              moe_ep=(moe_ep and arch.moe is not None))
    plan = sched.plan(arch, shape, ms)

    B, S = shape.global_batch, shape.seq_len
    # FS and uniform-DP shard the batch over every mesh axis
    full_batch = plan.uniform in ("FS", "DP") and shape.kind == "train"
    # layer-boundary activation sharding constraint (seq-sharding is
    # meaningless under FS/uniform-DP where `model` already carries batch)
    seq_ok = seq_ok and not full_batch
    meta = {"arch": arch_name, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "opt_preset": opt_preset, "microbatches": plan.microbatches,
            "seq_shard": seq_ok,
            "method": plan.plan.method, "feasible": plan.plan.feasible,
            "predicted": plan.plan.cost,
            "assignment": {k: str(v) for k, v in plan.assignment.items()}}

    if shape.kind != "train":
        # the engine's paged step at the shape's batch and length: one
        # prompt chunk (B = 1) for prefill, one token a slot for decode
        kind = "paged_prefill" if shape.kind == "prefill" else "paged_decode"
        geom = IR.ServeGeom(slots=B, max_len=S, block_size=SERVE_BLOCK,
                            prefill_chunk=SERVE_CHUNK)
        fn = ST.jit_step(kind, IR.build_step_fn(arch, kind))
        args = IR.step_arguments(arch, kind, geom, mesh=mesh, plan=plan)
        meta["serve_geom"] = dataclasses.asdict(geom)
        return fn, args, plan, meta, mesh

    pspecs = plan.param_specs()
    params_sds = _sds(C.abstract_params(arch), pspecs, mesh)
    tok_ns = NamedSharding(mesh, SH.token_spec(ms, B, full=full_batch))
    act_ns = NamedSharding(mesh, P(SH.batch_axes(ms, B, full=full_batch),
                                   "model" if seq_ok else None, None))
    fe_sds = None
    if arch.frontend == "vision":
        fe_sds = jax.ShapeDtypeStruct((B, arch.n_img_tokens, arch.d_model),
                                      jnp.bfloat16, sharding=tok_ns.update(
                                          spec=P(tok_ns.spec[0], None, None)))
    elif arch.frontend == "audio":
        fe_sds = jax.ShapeDtypeStruct((B, arch.encoder.seq_len, arch.d_model),
                                      jnp.bfloat16, sharding=tok_ns.update(
                                          spec=P(tok_ns.spec[0], None, None)))

    # "full" per-layer remat inside the layer scan: O(1) activation
    # memory in depth — the production default for these model sizes
    # ("selective" saves every dot output; see EXPERIMENTS.md §Perf)
    remat_policy = remat or "full"
    if plan.uniform == "FS" and remat is None:
        # FS: per-device batch is 1 — activations are tiny, so skip
        # grad accumulation (halves ZeRO gathers + grad reductions).
        # Keep full remat: under "selective" XLA holds every layer's
        # *gathered* weights for backward (53 GB/dev temps, §Perf it.3)
        plan.microbatches = 1
    opt_init, _ = optimizer = O.adamw(
        1e-4, quantized=(opt_preset == "adamw8bit"))
    opt_sds_raw = jax.eval_shape(opt_init, C.abstract_params(arch))
    opt_specs = SH.opt_state_specs(opt_sds_raw, pspecs, ms)
    opt_sds = _sds(opt_sds_raw, opt_specs, mesh)
    grad_ns = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    step = ST.make_train_step(arch, optimizer,
                              microbatches=plan.microbatches,
                              remat=remat_policy, act_sharding=act_ns,
                              grad_shardings=grad_ns)
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=tok_ns)
    batch = {"tokens": tok, "labels": tok}
    if fe_sds is not None:
        batch["frontend"] = fe_sds
    out_shardings = (jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
                     jax.tree.map(lambda s: NamedSharding(mesh, s),
                                  opt_specs),
                     None)
    fn = ST.jit_step("train", step, out_shardings=out_shardings)
    args = (params_sds, opt_sds, batch)
    meta["remat"] = remat_policy
    return fn, args, plan, meta, mesh


def model_flops(arch_name: str, shape_name: str) -> float:
    """6·N·D (train) / 2·N·D (inference), N = active params, D the tokens
    the lowered step processes."""
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    n_active = C.active_param_count(arch)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * SERVE_CHUNK          # one prompt chunk
    return 2.0 * n_active * shape.global_batch       # decode: 1 token/slot


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             save: bool = True, seq_shard: bool = False,
             opt8bit: bool = False, moe_ep: bool = False,
             tag: str = "") -> dict:
    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(arch, shape)
    rec = {"arch": arch_name, "shape": shape_name, "tag": tag,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        rec.update({"status": "skipped", "reason": why})
        _save(rec, save)
        return rec

    t0 = time.time()
    fn, args, plan, meta, mesh = build_cell(arch_name, shape_name,
                                            multi_pod=multi_pod,
                                            seq_shard=seq_shard,
                                            opt8bit=opt8bit, moe_ep=moe_ep)
    rec.update(meta)
    try:
        with jax.set_mesh(mesh):   # ambient mesh for bare-P constraints
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    except Exception as e:  # a failure here is a bug in our sharding config
        rec.update({"status": "FAILED", "error": f"{type(e).__name__}: {e}"})
        _save(rec, save)
        return rec

    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes") if hasattr(mem, k)}
        print(f"memory_analysis: {rec['memory']}")
    except Exception as e:
        rec["memory"] = {"error": str(e)}
    try:
        ca = compiled.cost_analysis()
        rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float))}
        print(f"cost_analysis[flops]: {rec['cost_analysis'].get('flops')}")
    except Exception as e:
        rec["cost_analysis"] = {"error": str(e)}

    rec["collectives"] = parse_collectives(compiled.as_text())
    rec["model_flops"] = model_flops(arch_name, shape_name)
    rec["lower_s"] = round(t_lower, 1)
    rec["compile_s"] = round(t_compile, 1)
    rec["status"] = "ok"
    _save(rec, save)
    return rec


def _save(rec: dict, save: bool):
    if not save:
        return
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    tag = f"__{rec['tag']}" if rec.get("tag") else ""
    name = (f"{rec['arch']}__{rec['shape']}__"
            f"{rec['mesh'].replace('x', '_')}{tag}.json")
    (OUT_DIR / name).write_text(json.dumps(rec, indent=1, default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-save", action="store_true")
    ap.add_argument("--seq-shard", action="store_true",
                    help="sequence-parallel activations (optimized mode)")
    ap.add_argument("--opt8bit", action="store_true",
                    help="int8 optimizer moments for any arch")
    ap.add_argument("--moe-ep", action="store_true",
                    help="EP-major MoE layout (a2a dispatch, no gathers)")
    ap.add_argument("--tag", default="", help="suffix for result files")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    n_fail = 0
    for a, s in cells:
        print(f"\n=== dry-run {a} x {s} ({'2x16x16' if args.multi_pod else '16x16'}) ===",
              flush=True)
        rec = run_cell(a, s, multi_pod=args.multi_pod, save=not args.no_save,
                       seq_shard=args.seq_shard, opt8bit=args.opt8bit,
                       moe_ep=args.moe_ep, tag=args.tag)
        print(f"-> {rec['status']} "
              f"(lower {rec.get('lower_s', '-')}s, compile {rec.get('compile_s', '-')}s) "
              f"coll={rec.get('collectives', {}).get('total_bytes', 0)/1e9:.2f}GB/dev "
              + (rec.get("reason", "") or rec.get("error", "")), flush=True)
        n_fail += rec["status"] == "FAILED"
    print(f"\ndry-run finished: {len(cells)} cells, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
