"""Distributed GEMM on the engine's 2D grid — the paper's §4.1 workload.

Three schedules, all computing C[m,k] = A[m,n] @ B[n,k] with every operand
in GRID layout (rows over the data axes, cols over 'model'):

- :func:`summa`          — faithful SUMMA: the n-dimension is streamed in
  panels; each panel's A-column-block is broadcast along mesh rows and
  B-row-block along mesh columns, local GEMMs accumulate into stationary C.
  This is Elemental's schedule, and the paper-faithful baseline.
- :func:`gemm_allgather` — one-shot variant: all-gather A along 'model' and
  B along 'data', then a single local GEMM. Fewer, larger messages; higher
  peak memory (the panel/streaming tradeoff the perf loop explores).
- :func:`gemm_xla`       — ``jnp.matmul`` under sharding constraints: lets
  XLA's SPMD partitioner choose the schedule (the beyond-paper comparison).

All local GEMMs go through :func:`repro.kernels.ops.matmul` (Pallas on TPU).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.core.layouts import AXIS_DATA, AXIS_MODEL, AXIS_POD, GRID
from repro.core import sharding as shardcore
from repro.kernels import ops


def _row_axes(mesh: Mesh):
    return tuple(a for a in (AXIS_POD, AXIS_DATA) if a in mesh.axis_names)


def _grid_dims(mesh: Mesh):
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    r = 1
    for a in _row_axes(mesh):
        r *= sizes[a]
    c = sizes.get(AXIS_MODEL, 1)
    return r, c


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[0]) % mult
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _pad_cols(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[1]) % mult
    return jnp.pad(x, ((0, 0), (0, pad))) if pad else x


def summa(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    panels: Optional[int] = None,
) -> jax.Array:
    """SUMMA C = A @ B, operands and result in GRID layout on ``mesh``.

    ``panels``: number of panels the contraction dimension is streamed in
    (defaults to lcm(grid rows, grid cols) — the coarsest exact panelling).
    Peak per-device memory beyond operands is one A-panel + one B-panel.
    """
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    r, c = _grid_dims(mesh)
    m, n = a.shape
    _, k = b.shape
    row_axes = _row_axes(mesh)

    if r == 1 and c == 1:
        return ops.matmul(a, b)

    # Panel count must be a multiple of lcm(r, c) so panels never straddle
    # shard boundaries; pad n to a multiple of n_panels (zero padding is
    # exact for GEMM), m to r, k to c.
    lcm_rc = math.lcm(r, c)
    n_panels = lcm_rc * max(1, -(-(panels or lcm_rc) // lcm_rc))
    a_p = _pad_cols(_pad_rows(a, r), n_panels)
    b_p = _pad_cols(_pad_rows(b, n_panels), c)
    np_ = a_p.shape[1]
    panel = np_ // n_panels
    loc_a_cols = np_ // c  # A's local column count
    loc_b_rows = np_ // r  # B's local row count

    grid_spec = GRID.partition_spec(mesh)
    a_p = jax.lax.with_sharding_constraint(a_p, NamedSharding(mesh, grid_spec))
    b_p = jax.lax.with_sharding_constraint(b_p, NamedSharding(mesh, grid_spec))

    row_entry = row_axes if len(row_axes) > 1 else row_axes[0]

    def local(a_loc: jax.Array, b_loc: jax.Array) -> jax.Array:
        # a_loc: [m/r, n/c]; b_loc: [n/r, k/c]
        row_rank = jax.lax.axis_index(row_axes[0])
        for ax in row_axes[1:]:
            row_rank = row_rank * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        col_rank = jax.lax.axis_index(AXIS_MODEL) if AXIS_MODEL in mesh.axis_names else 0

        m_loc = a_loc.shape[0]
        k_loc = b_loc.shape[1]

        def body(t, acc):
            # global panel t occupies columns [t*panel, (t+1)*panel) of A —
            # owned by mesh column `oc`; and rows of B owned by mesh row `orow`.
            start = t * panel
            oc = start // loc_a_cols
            off_a = start - oc * loc_a_cols
            a_slice = jax.lax.dynamic_slice_in_dim(a_loc, off_a, panel, axis=1)
            a_panel = jax.lax.psum(
                jnp.where(col_rank == oc, a_slice, jnp.zeros_like(a_slice)),
                AXIS_MODEL,
            ) if AXIS_MODEL in mesh.axis_names else a_slice

            orow = start // loc_b_rows
            off_b = start - orow * loc_b_rows
            b_slice = jax.lax.dynamic_slice_in_dim(b_loc, off_b, panel, axis=0)
            b_panel = jax.lax.psum(
                jnp.where(row_rank == orow, b_slice, jnp.zeros_like(b_slice)),
                row_axes,
            )
            return acc + ops.matmul(a_panel, b_panel, out_dtype=jnp.float32)

        acc = jnp.zeros((m_loc, k_loc), jnp.float32)
        acc = jax.lax.fori_loop(0, n_panels, body, acc)
        return acc.astype(a_loc.dtype)

    # check_vma=False: the local GEMM may be a Pallas kernel, whose output
    # carries no varying-axes type for shard_map to check.
    c_p = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(grid_spec, grid_spec),
        out_specs=grid_spec,
        check_vma=False,
    )(a_p, b_p)
    return c_p[:m, :k]


def gemm_allgather(a: jax.Array, b: jax.Array, mesh: Mesh) -> jax.Array:
    """All-gather-based GEMM: gather A along 'model', B along the row axes,
    one local GEMM. Minimal message count, maximal peak memory."""
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    r, c = _grid_dims(mesh)
    m, n = a.shape
    _, k = b.shape
    if r == 1 and c == 1:
        return ops.matmul(a, b)
    row_axes = _row_axes(mesh)
    # needs: r | m, c | k, lcm(r, c) | n (gathered dims line up exactly)
    lcm_rc = math.lcm(r, c)
    a_p = _pad_cols(_pad_rows(a, r), lcm_rc)
    b_p = _pad_cols(_pad_rows(b, lcm_rc), c)

    grid_spec = GRID.partition_spec(mesh)

    def local(a_loc: jax.Array, b_loc: jax.Array) -> jax.Array:
        a_row = a_loc
        if AXIS_MODEL in mesh.axis_names:
            a_row = jax.lax.all_gather(a_loc, AXIS_MODEL, axis=1, tiled=True)
        b_col = jax.lax.all_gather(b_loc, row_axes, axis=0, tiled=True)
        return ops.matmul(a_row, b_col)

    c_p = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(grid_spec, grid_spec),
        out_specs=grid_spec,
        check_vma=False,
    )(a_p, b_p)
    return c_p[:m, :k]


def gemm_xla(a: jax.Array, b: jax.Array, mesh: Mesh) -> jax.Array:
    """XLA-partitioned GEMM: constrain operands/result to GRID and let the
    SPMD partitioner pick the collective schedule."""
    spec = GRID.partition_spec(mesh)
    a = shardcore.constrain(a, spec, mesh)
    b = shardcore.constrain(b, spec, mesh)
    out = jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(a.dtype)
    return shardcore.constrain(out, spec, mesh)


SCHEDULES = {
    "summa": summa,
    "allgather": gemm_allgather,
    "xla": gemm_xla,
}


@functools.partial(jax.jit, static_argnames=("mesh", "schedule"))
def multiply(a: jax.Array, b: jax.Array, mesh: Mesh, *, schedule: str = "summa") -> jax.Array:
    """Dispatch by schedule name (the engine routine entry point)."""
    try:
        fn = SCHEDULES[schedule]
    except KeyError:
        raise ValueError(
            f"unknown GEMM schedule {schedule!r}; known: {sorted(SCHEDULES)}"
        ) from None
    return fn(a, b, mesh)
