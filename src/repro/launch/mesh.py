"""Production mesh definitions.

Single pod: (16, 16) = ("data", "model") — 256 chips (TPU v5e pod).
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips.

Functions, not module constants: importing this module never touches jax
device state (required so smoke tests see 1 CPU device).

Meshes are built with ``Auto`` axes: ``jax.make_mesh`` would default to
``Explicit`` ones, which the sharding rules here are not written for.
"""

from __future__ import annotations

from typing import Tuple

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(multi_pod: bool = False) -> Tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)


def make_host_mesh(shape: Tuple[int, ...] = None, axes=None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n) if n > 1 else (1, 1)
        axes = ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))
