"""Launch plumbing that every entry point shares: the compile cache, the
depth cut for a chip, host meshes, the hardware profile lookup, and
imports that leave the device alone."""
import os
import pathlib
import subprocess
import sys

import pytest
from jax.sharding import AxisType

from repro.configs import ARCHS, cut_depth, get_arch
from repro.core import hardware as HW
from repro.launch.mesh import make_host_mesh

REPO = pathlib.Path(__file__).resolve().parents[1]


def _python(code: str, env_update: dict) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               **env_update)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_defaults_to_the_checkout():
    got = _python("from repro.launch.compile_cache import "
                  "enable_compile_cache as e; print(e())", {})
    assert got == str(REPO / ".jax_cache")


def test_compile_cache_lands_where_the_environment_says(tmp_path):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "d = enable_compile_cache()\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
            "print(d)")
    got = _python(code, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path),
                         "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert got == str(tmp_path)
    assert any(p.name.startswith("jit__lambda") for p in tmp_path.iterdir())


def test_importing_the_package_leaves_the_device_alone():
    """A parent process that imported the model and serving code can
    still hand the chip to a child: no backend is opened at import."""
    got = _python("import repro.serving, repro.runtime.trainer\n"
                  "from jax._src import xla_bridge\n"
                  "print(xla_bridge.backends_are_initialized())", {})
    assert got == "False"


@pytest.mark.parametrize("name,layers", [("qwen3-8b", 8),
                                         ("mamba2-780m", 16),
                                         ("zamba2-2.7b", 18)])
def test_cut_depth_keeps_widths_and_whole_periods(name, layers):
    full = get_arch(name)
    cut = cut_depth(full, layers)
    assert cut.name == f"{name}-{layers}of{full.n_layers}L"
    assert cut.n_layers == layers
    for f in ("d_model", "n_heads", "n_kv_heads", "d_ff", "vocab", "ssm",
              "dtype", "param_dtype", "head_dim"):
        assert getattr(cut, f) == getattr(full, f), f
    for a, b in zip(cut.pattern, full.pattern):
        assert a.blocks == b.blocks
        assert a.repeat * full.n_layers == b.repeat * layers


def test_cut_depth_refuses_a_partial_period():
    with pytest.raises(ValueError, match="whole periods"):
        cut_depth(ARCHS["zamba2-2.7b"], 10)       # 9 periods of 6 layers


def test_host_mesh_axes_are_auto():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_hardware_profile_by_device_kind():
    assert HW.profile_for_device_kind("TPU v5 lite") is HW.TPU_V5E
    with pytest.raises(ValueError, match="no hardware profile"):
        HW.profile_for_device_kind("TPU v99")
    assert HW.local_profile() is HW.TPU_V5E      # off a TPU: the target
