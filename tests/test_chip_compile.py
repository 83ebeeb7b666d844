"""The Pallas kernels compile for a TPU v5e at real widths, and the paged
decode updates its KV pools in place there.

Each test compiles one program ahead of time for one chip of a described
``v5e:2x2`` topology (no chip attached, nothing runs).  The kernel tests
check that the program holds the Mosaic kernel.  This catches what
interpret mode cannot: block shapes off the TPU tiling, more VMEM than a
kernel may use.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as FA
from repro.kernels import rmsnorm as RN
from repro.kernels import ssd_scan as SSD
from serving_fixtures import STRUCT_ARCH, paged_decode_compiled, pool_moves


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip cannot be read back from
    the persistent cache without that chip; keep such compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_for_v5e(one_chip, no_compile_cache):
    qkv = ((1, 32, 2048, 128), jnp.bfloat16)       # qwen3-8b heads, S 2048
    hlo = _compile(lambda q, k, v: FA.flash_attention(q, k, v,
                                                      interpret=False),
                   one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


def test_ssd_scan_compiles_for_v5e(one_chip, no_compile_cache):
    B, H, S, P, N = 2, 48, 1024, 64, 128           # mamba2-780m widths
    hlo = _compile(lambda *xs: SSD.ssd_scan(*xs, chunk=128, interpret=False),
                   one_chip,
                   ((B, H, S, P), jnp.bfloat16), ((B, H, S, N), jnp.bfloat16),
                   ((B, H, S, N), jnp.bfloat16), ((B, H, S), jnp.float32),
                   ((B, H, S), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_rmsnorm_compiles_for_v5e(one_chip, no_compile_cache):
    hlo = _compile(lambda x, s: RN.rmsnorm(x, s, interpret=False), one_chip,
                   ((4096, 4096), jnp.bfloat16), ((4096,), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_paged_decode_updates_pools_in_place_on_v5e(one_chip,
                                                    no_compile_cache):
    """The bf16 paged decode writes its new K/V rows into the stacked pools
    it carries through the layer scan: no copy, slice or write-back of a
    layer's pool or of the stack, and the cache aliased input to output.
    KV heads of 8 x 128 as qwen3-8b's, and pools too large for the chip's
    on-core memory (a small one is staged there whole)."""
    arch = dataclasses.replace(STRUCT_ARCH, n_heads=16, n_kv_heads=8,
                               head_dim=128)
    blocks, block_size = 2048, 16
    compiled, cache_bytes = paged_decode_compiled(
        arch, num_blocks=blocks, block_size=block_size, slots=8,
        table_len=8, pool_dtype=jnp.bfloat16, sharding=one_chip)
    pool_shape = (blocks, block_size, arch.n_kv_heads,
                  arch.resolved_head_dim)
    assert pool_moves(compiled.as_text(), pool_shape,
                      arch.pattern[0].repeat) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 2     # one stacked pool
