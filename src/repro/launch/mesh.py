"""Production mesh builders.

Functions (not module-level constants) so importing this module never touches
jax device state.  Single pod: 16x16 = 256 chips (data, model).  Multi-pod:
2x16x16 = 512 chips (pod, data, model) — the `pod` axis is the slow-link
(DCN) axis carrying data parallelism + pod-sharded ZeRO only.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.core.costmodel import MeshShape


def _auto(n: int) -> tuple:
    """Auto axes: the model code places activations with
    ``with_sharding_constraint``, which only names Auto axes (jax's default
    for ``make_mesh`` is Explicit)."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(len(axes)))


def mesh_shape_of(mesh) -> MeshShape:
    d = dict(zip(mesh.axis_names, mesh.devices.shape))
    return MeshShape(data=d.get("data", 1), model=d.get("model", 1),
                     pod=d.get("pod", 1))


def make_host_mesh(n_devices: int | None = None, model: int = 1):
    """Small CPU mesh for tests/examples (uses however many host devices
    exist, factored as (data, model))."""
    n = n_devices or len(jax.devices())
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"), _auto(2))
