"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE any jax
import; everything else sees the real device count.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def _auto_mesh(shape, axes):
    """Mesh whose axes are all `Auto`: the LM stack leaves output
    shardings to the partitioner (its rules name only parameter and
    batch specs), which jax's default `Explicit` axes refuse at every
    gather or contraction whose output sharding is ambiguous."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16 x 16 = 256 chips (data, model).
    Multi-pod: 2 x 16 x 16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / smoke runs)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"))
