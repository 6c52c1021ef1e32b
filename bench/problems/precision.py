"""Operand rounding for the lower-precision controls.

A control computes the reference with every operand of every product rounded
to a lower precision and accumulated in float32, as the chip's matrix unit
does: ``bf16`` rounds to bfloat16; ``int8`` and ``fp8`` (e4m3: 4 exponent
and 3 mantissa bits) scale by the largest magnitude along ``axis`` (per row
of a stored matrix, per tensor otherwise) before rounding.

Rounding goes through ``lax.reduce_precision``: XLA may drop a round trip
through a narrower type (``xla_allow_excess_precision``), never this op.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("bf16", "fp8", "int8")


def _scaled(x, axis, top, cast):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return cast(x / scale) * scale


def _e4m3(y):
    return jax.lax.reduce_precision(y, 4, 3)


def rounder(precision: str):
    """(round(x, axis=None), the dot precision) for ``precision``."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")
    hi = jax.lax.Precision.HIGHEST
    if precision == "bf16":
        return (lambda x, axis=None: jax.lax.reduce_precision(x, 8, 7)), hi
    if precision == "fp8":
        # 240 is the largest finite value with 4 exponent bits under IEEE rules.
        return (lambda x, axis=None: _scaled(x, axis, 240.0, _e4m3)), hi

    def int8(x, axis=None):
        return _scaled(x, axis, 127.0, lambda y: jnp.clip(jnp.round(y), -127, 127))

    return int8, hi
