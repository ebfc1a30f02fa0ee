"""Device-mesh construction for the sharded backends.

Defined as functions so importing this module never touches jax device
state (tests set JAX_PLATFORMS / XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_host_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the first ``data * model`` devices."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def make_device_mesh():
    """Mesh over every device of the process, all on the ``data`` axis:
    the sharded backends' default, so a solve spans the chips it has."""
    return make_host_mesh(jax.device_count(), 1)
