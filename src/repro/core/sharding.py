"""Mesh-axis conventions and sharding rules shared by the whole framework.

One place defines what each mesh axis means; everything else (engine layouts,
model parameter shardings, train/serve steps, the dry-run) derives from here.

Axes:
  - ``pod``   — pure data parallelism across pods (gradient all-reduce crosses
                the inter-pod links once per step).
  - ``data``  — intra-pod data parallelism; also the FSDP axis for weights and
                the row axis of engine GRID layouts.
  - ``model`` — tensor parallelism (attention heads / MLP hidden / experts) and
                the column axis of engine GRID layouts.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.layouts import AXIS_DATA, AXIS_MODEL, AXIS_POD


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the global batch is sharded over."""
    return tuple(a for a in (AXIS_POD, AXIS_DATA) if a in mesh.axis_names)


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes weights are fully-sharded over (ZeRO-3 style)."""
    return tuple(a for a in (AXIS_DATA,) if a in mesh.axis_names)


def model_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in (AXIS_MODEL,) if a in mesh.axis_names)


def _entry(axes: Tuple[str, ...]):
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def batch_entry(mesh: Mesh):
    return _entry(batch_axes(mesh))


def fsdp_entry(mesh: Mesh):
    return _entry(fsdp_axes(mesh))


def model_entry(mesh: Mesh):
    return _entry(model_axes(mesh))


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical-dimension -> mesh-axes table, resolved per mesh.

    Model code annotates parameters/activations with logical axis names; this
    table maps them to mesh axes. Swapping the table is how the perf loop
    changes sharding schemes without touching model code.
    """

    batch: Tuple[str, ...]
    fsdp: Tuple[str, ...]        # weight row-shard axis (ZeRO)
    tensor: Tuple[str, ...]      # tensor-parallel axis
    expert: Tuple[str, ...]      # expert-parallel axis
    sequence: Tuple[str, ...] = ()   # sequence/context parallel axis (opt-in)

    @staticmethod
    def default(mesh: Mesh) -> "ShardingRules":
        return ShardingRules(
            batch=batch_axes(mesh),
            fsdp=fsdp_axes(mesh),
            tensor=model_axes(mesh),
            expert=model_axes(mesh),
            sequence=(),
        )

    @staticmethod
    def zero3(mesh: Mesh) -> "ShardingRules":
        """ZeRO-3: weights fully sharded over data AND model axes, no tensor
        parallelism — trades activation all-reduces for per-layer parameter
        all-gathers (the deepseek-33b hillclimb hypothesis)."""
        return ShardingRules(
            batch=batch_axes(mesh),
            fsdp=tuple(a for a in (AXIS_DATA, AXIS_MODEL) if a in mesh.axis_names),
            tensor=(),
            expert=model_axes(mesh),
            sequence=(),
        )

    @staticmethod
    def zero3_full(mesh: Mesh) -> "ShardingRules":
        """ZeRO-3 done right: with no tensor axis, the model axis must join
        the batch axes (pure 256-way data parallelism), otherwise per-device
        compute inflates by the idle axis — the refuted first zero3 attempt."""
        axes = tuple(a for a in (AXIS_POD, AXIS_DATA, AXIS_MODEL) if a in mesh.axis_names)
        return ShardingRules(
            batch=axes,
            fsdp=tuple(a for a in (AXIS_DATA, AXIS_MODEL) if a in mesh.axis_names),
            tensor=(),
            expert=model_axes(mesh),
            sequence=(),
        )

    @staticmethod
    def seq_parallel(mesh: Mesh) -> "ShardingRules":
        """Default rules + sequence sharding of residuals over the model
        axis (Megatron sequence parallelism): activation all-reduces become
        reduce-scatter + all-gather pairs."""
        base = ShardingRules.default(mesh)
        return dataclasses.replace(base, sequence=model_axes(mesh))

    @staticmethod
    def fsdp_only(mesh: Mesh) -> "ShardingRules":
        """Pure data-parallel scheme — the 'Spark-like' 1D world: no tensor
        axis; the model axis is folded into batch. Used as the paper-faithful
        'what Spark alone gives you' comparison point."""
        axes = tuple(a for a in (AXIS_POD, AXIS_DATA, AXIS_MODEL) if a in mesh.axis_names)
        return ShardingRules(batch=axes, fsdp=(), tensor=(), expert=(), sequence=())

    def resolve(self, logical: Tuple[Optional[str], ...]) -> P:
        """Map a tuple of logical dim names to a PartitionSpec."""
        table = {
            "batch": _entry(self.batch),
            "fsdp": _entry(self.fsdp),
            "tensor": _entry(self.tensor),
            "expert": _entry(self.expert),
            "sequence": _entry(self.sequence),
            None: None,
        }
        entries = []
        used: set = set()
        for name in logical:
            if name not in table:
                raise KeyError(f"unknown logical axis {name!r}")
            entry = table[name]
            # a mesh axis may appear at most once per spec: first dim wins
            # (e.g. zero3_full on MoE weights: 'model' serves the expert dim,
            # so the fsdp entry of the same tensor drops it)
            if entry is None:
                entries.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(a for a in axes if a not in used)
            used.update(kept)
            entries.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        return P(*entries)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Build a mesh from the available devices (works on the 1-CPU test env
    when shape == (1,)*n, and on the 512-host-device dry-run env)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def single_device_mesh() -> Mesh:
    """A (1, 1) ('data','model') mesh on the default device — used by smoke
    tests and CPU examples so the same sharded code paths run everywhere."""
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, (AXIS_DATA, AXIS_MODEL))


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def _axis_prod(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes[a]
    return n


def divisible_spec(shape: Tuple[int, ...], spec: P, mesh: Mesh) -> P:
    """Drop sharding on dims the mesh doesn't divide evenly.

    ``with_sharding_constraint`` / pjit out-shardings reject uneven dims;
    this keeps every legal annotation and silently replicates the rest
    (XLA would have padded anyway — we prefer the explicit fallback).
    """
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        out.append(entry if dim % _axis_prod(mesh, entry) == 0 else None)
    return P(*out)


def constrain(x, spec: P, mesh: Mesh):
    """Divisibility-safe ``with_sharding_constraint``."""
    safe = divisible_spec(tuple(x.shape), spec, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, safe))
