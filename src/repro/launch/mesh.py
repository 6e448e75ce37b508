"""Production mesh construction.

Kept as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real single CPU device.

Every mesh has Auto axes: the model code places activations with
`with_sharding_constraint` (parallel/sharding.py), which only accepts
Auto axes (`jax.make_mesh` defaults to Explicit ones).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Mesh of the given shape with Auto axes (tests use tiny ones)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: one pod = 16x16 = 256 chips; two pods = 512 chips."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_local_mesh():
    """Single-device mesh for CPU smoke paths."""
    return make_mesh((1, 1), ("data", "model"))
