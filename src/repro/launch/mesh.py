"""Production and local meshes.

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

# device_kind of the production mesh's chips (TPU v5e), for peak lookups
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """TPU v5e mesh: 16x16 (one pod, 256 chips) or 2x16x16 (two pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1) -> Mesh:
    """Mesh over whatever devices exist (CPU tests: usually 1)."""
    devices = np.array(jax.devices())
    n = devices.size
    mp = model_parallel if n % model_parallel == 0 else 1
    return Mesh(devices.reshape(n // mp, mp), ("data", "model"))
