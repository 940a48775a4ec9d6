"""Production mesh definitions.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization. Every axis is explicitly Auto.
"""

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 chips across 2 pods."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def single_device_mesh():
    return make_mesh((1,), ("data",))
