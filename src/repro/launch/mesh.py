"""Production meshes.  Functions, not module-level constants — importing this
module never touches jax device state (the dry-run sets
xla_force_host_platform_device_count *before* any jax import).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

SINGLE_POD = (16, 16)                 # 256 chips/pod (v5e pod slice)
MULTI_POD = (2, 16, 16)               # 2 pods = 512 chips


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def chips(mesh) -> int:
    return int(mesh.devices.size)
