"""Pallas kernels (flash attention, SSD chunked scan, RMSNorm), their jnp
oracles (``ref.py``) and model-layout wrappers (``ops.py``)."""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Interpret mode on the CPU (where the tests validate the kernels),
    compiled Mosaic on a TPU, and an error on any other backend: a kernel
    silently interpreted on an accelerator would hide the device."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run compiled on 'tpu' or "
                       f"interpreted on 'cpu'; backend is {backend!r}")
