"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The production target is a TPU v5e pod of 16x16 =
256 chips ('data' x 'model'); the multi-pod mesh stacks 2 pods on a leading
'pod' axis (512 chips) whose cross-pod DCI links carry only batch-gradient
traffic (see ``repro.parallel.sharding``).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_debug_mesh"]


def _mesh(shape, axes, devices):
    """Mesh with Auto axes: the sharding rules place arrays through
    ``with_sharding_constraint``, which refuses Explicit axes (the
    ``jax.make_mesh`` default)."""
    return jax.make_mesh(
        shape, axes, devices=devices, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — "
            "run under launch/dryrun.py (it forces 512 host devices) or on "
            "real hardware"
        )
    return _mesh(shape, axes, devices[:need])


def make_debug_mesh(data: int, model: int, pod: int = 0):
    """Small mesh over however many host devices exist (tests)."""
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    need = math.prod(shape)
    return _mesh(shape, axes, jax.devices()[:need])
