"""Production meshes.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and only the
dry-run process sets the 512-host-device XLA flag).
"""

from __future__ import annotations

import jax

from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips).

    ``pod`` is the slow-interconnect axis (DP replicas by default; the
    pipeline schedule in distributed.pipeline can claim it instead).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
