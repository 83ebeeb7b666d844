"""Sharding-layer invariants for every assigned arch x strategy:
spec trees mirror param trees exactly and every spec divides its dim."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.core import components as C
from repro.core import sharding as SH
from repro.core.costmodel import MeshShape
from repro.core.strategy import Strategy, UNIFORM_STRATEGIES
from repro.models import transformer as T

MESHES = [MeshShape(16, 16), MeshShape(16, 16, pod=2)]
SIZES = {"data": 16, "model": 16, "pod": 2}


def _check_divisible(spec, shape, where):
    for i, ax in enumerate(spec):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        total = 1
        for a in axes:
            total *= SIZES[a]
        assert shape[i] % total == 0, (where, spec, shape)


@pytest.mark.parametrize("name", sorted(ARCHS))
@pytest.mark.parametrize("strategy", [Strategy.DP, Strategy.MP, Strategy.HP,
                                      Strategy.FS])
def test_param_specs_mirror_and_divide(name, strategy):
    arch = ARCHS[name]
    aparams = C.abstract_params(arch)
    comps = C.components_for_shape(arch,
        __import__("repro.configs.base", fromlist=["SHAPES"]).SHAPES["train_4k"])
    assignment = {c.name: strategy for c in comps}
    for mesh in MESHES:
        specs = SH.param_specs(arch, assignment, mesh)
        # same tree structure
        assert jax.tree.structure(specs) == jax.tree.structure(aparams)
        flat_p = jax.tree.leaves(aparams)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        for leaf, spec in zip(flat_p, flat_s):
            assert len(spec) <= len(leaf.shape), (name, spec, leaf.shape)
            _check_divisible(spec, leaf.shape, name)


@pytest.mark.parametrize("name", ["qwen3-8b", "zamba2-2.7b",
                                  "deepseek-v3-671b", "whisper-medium",
                                  "llama-3.2-vision-90b"])
def test_cache_specs_mirror_cache_tree(name):
    import jax.numpy as jnp
    arch = ARCHS[name]
    from repro.configs.base import SHAPES
    comps = C.components_for_shape(arch, SHAPES["decode_32k"])
    assignment = {c.name: Strategy.MP for c in comps}
    mesh = MeshShape(16, 16)
    cache_sds = jax.eval_shape(
        lambda: T.init_paged_cache(arch, 1025, 16, jnp.bfloat16, slots=128))
    specs = SH.paged_cache_specs(arch, assignment, mesh)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(cache_sds)
    for leaf, spec in zip(jax.tree.leaves(cache_sds),
                          jax.tree.leaves(specs,
                                          is_leaf=lambda x: isinstance(x, P))):
        _check_divisible(spec, leaf.shape, name)


def test_batch_axes_fallbacks():
    ms = MeshShape(16, 16)
    assert SH.batch_axes(ms, 256) == "data"
    assert SH.batch_axes(ms, 1) is None
    assert SH.batch_axes(ms, 256, full=True) == ("data", "model")
    ms2 = MeshShape(16, 16, pod=2)
    assert SH.batch_axes(ms2, 256) == ("pod", "data")
    assert SH.batch_axes(ms2, 512, full=True) == ("pod", "data", "model")
