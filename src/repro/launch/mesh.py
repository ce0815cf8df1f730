"""Mesh builders.

FUNCTIONS, not module-level constants, so importing this module never
touches jax device state (dry-runs must set XLA_FLAGS first).

Every axis is ``AxisType.Auto``: the model code leaves placement to GSPMD
(sharding constraints on activations, replicated params over the data
axes), which is what ``jax.make_mesh``'s default of explicit axes would
refuse — e.g. an embedding gather whose out-sharding is ambiguous.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with auto-sharded axes; ``devices`` defaults to
    all of ``jax.devices()`` and may be described (compile-only) devices."""
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=auto)
    return Mesh(np.asarray(devices).reshape(shape), axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips).

    When the process exposes more placeholder devices than the mesh needs
    (the dry-run forces 512), the single-pod mesh takes the first 256.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(f"mesh needs {need} devices, have {len(devices)}")
    if len(devices) != need:
        return make_mesh(shape, axes, devices[:need])
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 4, model: int = 2, *, pods: int = 0):
    """Small mesh for subprocess tests (needs matching fake device count)."""
    if pods:
        return make_mesh((pods, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
