"""Contractions at a named precision mode.

``f32``: float32 operands at ``Precision.HIGHEST`` (six bf16 passes on the
TPU's MXU, exact float32 products elsewhere): the references' own.

``bf16x3``: three bf16 passes, ``hi*hi + hi*lo + lo*hi`` with the products
accumulated in float32: what ``Precision.HIGH`` does on the MXU, written
out so that it computes the same on any backend.  The control of a
configuration that states float32 at ``highest``: the nearest precision
below it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16x3")
HIGHEST = jax.lax.Precision.HIGHEST
CONTROL = {"highest": "bf16x3"}


def _dot(a, b):
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def matmul(a, b, mode: str = "f32"):
    """``a @ b`` as float32, computed at ``mode``."""
    if mode == "f32":
        return jnp.matmul(a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    if mode == "bf16x3":
        bf = jnp.bfloat16
        a_hi, b_hi = a.astype(bf), b.astype(bf)
        a_lo = (a - a_hi.astype(jnp.float32)).astype(bf)
        b_lo = (b - b_hi.astype(jnp.float32)).astype(bf)
        return _dot(a_hi, b_hi) + _dot(a_hi, b_lo) + _dot(a_lo, b_hi)
    raise ValueError(f"unknown precision mode {mode!r}; want one of {MODES}")


def control_mode(stated: str) -> str:
    """The control's mode for the matmul precision a configuration states."""
    if stated not in CONTROL:
        raise ValueError(f"no control for the stated precision {stated!r}; "
                         f"have {sorted(CONTROL)}")
    return CONTROL[stated]
