"""Production meshes for the TPU v5e target.

Importing this module never touches jax device state — meshes are built by
functions only (the dry-run sets ``xla_force_host_platform_device_count``
before any jax import; tests and benches see the real single device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

#: TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12      # per chip [FLOP/s]
HBM_BW = 819e9                # per chip [B/s]
ICI_BW = 50e9                 # per link [B/s]
HBM_BYTES = 16 * 1024**3      # per chip

SINGLE_POD_SHAPE = (16, 16)
MULTI_POD_SHAPE = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (CPU tests)."""
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


def n_chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size
