"""Production mesh builders.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — jax locks the device count on first backend
initialization, and only launch/dryrun.py sets the 512-device host platform.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the "pod" axis carries
    data parallelism across the DCN/ICI boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """All-Auto axis types, like the engine meshes in core/parallel_exec.py
    (``jax.make_mesh`` would default to Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
