"""Lowered-IR extraction for the jitted serving steps.

The jax-facing half of tracecheck: builds ShapeDtypeStruct stand-ins for
every registered serving step (make_paged_prefill_step /
make_paged_decode_step / make_slot_admit_step) at the engine's real call
shapes, lowers + compiles them (no allocation), and extracts the raw
facts — donation flags, buffer aliasing, primitive census, output
structure/shardings, XLA cost analysis — that the analyzers in
``repro.analysis.tracecheck`` turn into findings.

Everything here is pure extraction: no thresholds, no verdicts.  The
engine's geometry conventions are mirrored exactly (prefill is a B=1
chunk, decode advances every slot, block tables are padded to
``max_blocks_per_seq``), so what gets lowered IS what serves.
launch/dryrun.py lowers its prefill and decode cells through the same
builders (``step_arguments``, ``build_step_fn``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.extend import core as jcore
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core.asa import AdaptiveScheduler
from repro.launch.mesh import mesh_shape_of
from repro.models import transformer as T
from repro.runtime import steps as ST
from repro.serving.cache_manager import SLOT_STATE_KINDS
from repro.serving.paged_cache import blocks_for
from repro.serving.sampling import make_sampler


@dataclasses.dataclass(frozen=True)
class ServeGeom:
    """One serving geometry: the shapes every step is traced at.

    ``table_len`` (= max_blocks_per_seq * block_size) is the padded
    attention span — paged attention scores every query against that full
    (masked) capacity, which makes it the effective T for static cost.
    """
    slots: int = 4
    max_len: int = 64
    block_size: int = 8
    prefill_chunk: int = 16

    @property
    def max_blocks_per_seq(self) -> int:
        return blocks_for(self.max_len, self.block_size)

    @property
    def num_blocks(self) -> int:
        return self.slots * self.max_blocks_per_seq + 1      # +1: null block

    @property
    def table_len(self) -> int:
        return self.max_blocks_per_seq * self.block_size


def step_kinds(arch: ArchConfig) -> tuple[str, ...]:
    """The jitted step kinds the engine registers for this arch."""
    kinds = {k for seg in arch.pattern for k in seg.blocks}
    out = ("paged_prefill", "paged_decode")
    if kinds & SLOT_STATE_KINDS:
        out += ("slot_admit",)
    return out


def build_plan(arch: ArchConfig, geom: ServeGeom, mesh):
    """The same ASA plan the engine builds for this serve shape."""
    shape = ShapeSpec("serve", geom.max_len, geom.slots, "decode")
    return AdaptiveScheduler(faithful=False).plan(
        arch, shape, mesh_shape_of(mesh))


def _cache_dtype(arch: ArchConfig):
    return jnp.float32 if arch.dtype == "float32" else jnp.bfloat16


def _attach(tree, specs, mesh):
    """Attach NamedShardings to a ShapeDtypeStruct tree (dryrun idiom)."""
    return jax.tree.map(
        lambda leaf, s: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                             sharding=NamedSharding(mesh, s)),
        tree, specs)


def _sds(shape, dtype, mesh=None):
    if mesh is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P()))


def frontend_sds(arch: ArchConfig, mesh=None) -> Optional[jax.ShapeDtypeStruct]:
    """Admission-time modality input, iff the arch consumes one: vision
    patch embeddings or audio frame embeddings (see transformer.admit_slot)."""
    if arch.frontend == "vision":
        return _sds((1, arch.n_img_tokens, arch.d_model), jnp.float32, mesh)
    if arch.frontend == "audio":
        return _sds((1, arch.encoder.seq_len, arch.d_model), jnp.float32, mesh)
    return None


def step_arguments(arch: ArchConfig, kind: str, geom: ServeGeom, *,
                   mesh=None, plan=None) -> tuple:
    """ShapeDtypeStruct argument tuple for one step kind, at exactly the
    shapes serving/engine.py calls it with.  With ``mesh`` the params and
    cache carry the plan's NamedShardings (host-side operands replicated),
    mirroring the device_put layout of a live engine."""
    if mesh is not None and plan is None:
        plan = build_plan(arch, geom, mesh)
    params = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), arch))
    cache = jax.eval_shape(lambda: T.init_paged_cache(
        arch, geom.num_blocks, geom.block_size, dtype=_cache_dtype(arch),
        slots=geom.slots))
    if mesh is not None:
        params = _attach(params, plan.param_specs(), mesh)
        cache = _attach(cache, plan.paged_cache_specs(), mesh)

    if kind == "slot_admit":
        args = (params, cache, _sds((), jnp.int32, mesh))
        fe = frontend_sds(arch, mesh)
        return args + ((fe,) if fe is not None else ())

    B = 1 if kind == "paged_prefill" else geom.slots
    S = geom.prefill_chunk if kind == "paged_prefill" else 1
    args = (params, cache,
            _sds((B, S), jnp.int32, mesh),                    # tokens
            _sds((B,), jnp.int32, mesh))                      # positions
    args += (_sds((B, geom.max_blocks_per_seq), jnp.int32, mesh),)
    if kind == "paged_prefill":
        args += (_sds((B,), jnp.int32, mesh),)                # new_lens
    args += (_sds((B,), jnp.int32, mesh),)                    # slot_ids
    # fused per-row sampler parameters (temperature, top_k, top_p, seeds)
    args += (_sds((B,), jnp.float32, mesh), _sds((B,), jnp.int32, mesh),
             _sds((B,), jnp.float32, mesh), _sds((B,), jnp.uint32, mesh))
    return args


def build_step_fn(arch: ArchConfig, kind: str):
    """The un-jitted step callable the engine registers for ``kind``."""
    if kind == "paged_prefill":
        return ST.make_paged_prefill_step(arch,
                                          sampler=make_sampler(arch.vocab))
    if kind == "paged_decode":
        return ST.make_paged_decode_step(arch,
                                         sampler=make_sampler(arch.vocab))
    if kind == "slot_admit":
        return ST.make_slot_admit_step(arch)
    raise ValueError(f"unknown serving step kind {kind!r}")


@dataclasses.dataclass
class LoweredStep:
    """One step lowered (and lazily compiled) against its SDS arguments."""
    arch: ArchConfig
    kind: str
    fn: object                     # the un-jitted callable
    args: tuple                    # SDS argument tuple
    lowered: object                # jax.stages.Lowered
    _compiled: object = None

    @property
    def compiled(self):
        if self._compiled is None:
            self._compiled = self.lowered.compile()
        return self._compiled

    @property
    def cache_index(self) -> int:
        return 1                   # (params, cache, ...) for every kind


@functools.lru_cache(maxsize=None)
def _lowered_cache():
    return {}


def lower_step(arch: ArchConfig, kind: str, geom: ServeGeom, *,
               mesh=None, plan=None) -> LoweredStep:
    """Lower one serving step.  Results are memoized per
    (arch, kind, geom, meshful) — lowering is the expensive part and the
    analyzers share it freely."""
    key = (arch.name, kind, geom, mesh is not None)
    cache = _lowered_cache()
    if key not in cache:
        fn = build_step_fn(arch, kind)
        args = step_arguments(arch, kind, geom, mesh=mesh, plan=plan)
        cache[key] = LoweredStep(arch, kind, fn, args,
                                 ST.jit_step(kind, fn).lower(*args))
    return cache[key]


# ---------------------------------------------------------------------------
# extraction reports
# ---------------------------------------------------------------------------

def _leaf_bytes(leaf) -> int:
    return math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize \
        if leaf.shape else jnp.dtype(leaf.dtype).itemsize


def tree_bytes(tree) -> int:
    return sum(_leaf_bytes(x) for x in jax.tree.leaves(tree))


def donation_report(ls: LoweredStep) -> dict:
    """Which positional args are donated (from ``lowered.args_info``), and
    whether the runtime will actually elide them (``alias_size_in_bytes``
    of the buffer assignment)."""
    infos = ls.lowered.args_info
    # args_info mirrors the (args, kwargs) calling convention — unwrap to
    # the positional tuple (serving steps take no kwargs)
    if isinstance(infos, tuple) and len(infos) == 2 \
            and isinstance(infos[1], dict) and not infos[1]:
        infos = infos[0]
    donated, arg_bytes = [], []
    for i, info in enumerate(infos):
        leaves = jax.tree.leaves(info)
        arg_bytes.append(sum(
            math.prod(l.shape) * jnp.dtype(l.dtype).itemsize for l in leaves))
        if leaves and all(l.donated for l in leaves):
            donated.append(i)
    mem = ls.compiled.memory_analysis()
    return {
        "donated_args": tuple(donated),
        "arg_bytes": tuple(arg_bytes),
        "cache_bytes": arg_bytes[ls.cache_index],
        "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
    }


def _walk_jaxpr(jaxpr, prims: set):
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in _iter_subjaxprs(v):
                _walk_jaxpr(sub, prims)


def _iter_subjaxprs(value):
    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _iter_subjaxprs(v)


def primitive_census(ls: LoweredStep) -> frozenset:
    """Every primitive name reachable in the step's jaxpr (recursing into
    scan/cond/remat/... sub-jaxprs)."""
    prims: set = set()
    _walk_jaxpr(jax.make_jaxpr(ls.fn)(*ls.args).jaxpr, prims)
    return frozenset(prims)


def output_structure(ls: LoweredStep):
    """ShapeDtypeStruct pytree of the step's outputs."""
    return jax.eval_shape(ls.fn, *ls.args)


def output_shardings(ls: LoweredStep):
    """Compiled output shardings, as a pytree matching output_structure."""
    return ls.compiled.output_shardings


def cost_report(ls: LoweredStep) -> dict:
    """XLA's static cost analysis of the compiled step: total FLOPs, bytes
    accessed, and the peak temp-buffer footprint."""
    ca = ls.compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):      # some backends wrap in a list
        ca = ca[0] if ca else {}
    mem = ls.compiled.memory_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
    }
