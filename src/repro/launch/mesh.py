"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run driver
must set ``XLA_FLAGS`` before the first jax call.

Production target (TPU v5e):
  - single pod:  (16, 16)      axes ('data', 'model')   — 256 chips
  - multi-pod:   (2, 16, 16)   axes ('pod', 'data', 'model') — 512 chips

The 'pod' axis carries pure data parallelism (one gradient all-reduce per
step crosses the inter-pod links); 'data' is intra-pod data parallel +
FSDP; 'model' is tensor/expert parallel and the engine grid's column axis.
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.core.layouts import AXIS_DATA, AXIS_MODEL, AXIS_POD

SINGLE_POD_SHAPE: Tuple[int, int] = (16, 16)
MULTI_POD_SHAPE: Tuple[int, int, int] = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = (AXIS_POD, AXIS_DATA, AXIS_MODEL) if multi_pod else (AXIS_DATA, AXIS_MODEL)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape: Tuple[int, ...] = (2, 2)) -> Mesh:
    """Small mesh for CPU multi-device tests (requires forced host devices)."""
    axes = ((AXIS_POD, AXIS_DATA, AXIS_MODEL) if len(shape) == 3 else (AXIS_DATA, AXIS_MODEL))
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
