"""Fused pad/strip relayout kernels — the bridge's divisibility padding on device.

The bridge pads uneven matrices up to the destination layout's shard-count
multiples before ``device_put`` and slices the padding off on collect/refill
(DESIGN.md §7). As host-side ``jnp.pad`` + slice passes those cost an extra
materialization each way; these kernels fuse the mask/copy into a single
tiled device pass (DESIGN.md §10), following the grid idiom of
:mod:`repro.kernels.matmul`.

- :func:`pad_to` grids over the *physical* (padded) output. Each input block
  shares the output block's index map, so edge tiles read out of bounds; a
  ``broadcasted_iota`` mask against the logical extent selects real values
  and writes zeros elsewhere — OOB reads never reach the output.
- :func:`strip_to` grids over the *logical* output with the same block on
  both sides; partial edge output tiles are write-masked by Pallas and the
  body is a straight block copy.

Blocks follow Mosaic's tiling rule: the row block is a multiple of
:data:`ROW_ALIGN` and the column block a multiple of :data:`COL_ALIGN`, or
the full extent where input and output share it. Partial edge blocks (and a
block larger than a small array) are legal; the masks above keep them exact.

Bit-exactness against :mod:`repro.kernels.ref` is property-tested in
tests/test_padded_roundtrip.py; ``ops.py`` dispatches here on TPU (or under
``REPRO_FORCE_PALLAS=interpret``) and to the jnp references on CPU.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Sublane / lane tiling: 32 rows covers the packed 8/16/32-row tiles of
#: 32-, 16- and 8-bit dtypes; 128 columns is one lane tile.
ROW_ALIGN = 32
COL_ALIGN = 128
#: Block caps: a 512x512 f32 block is 1 MiB, so input + output blocks,
#: double-buffered, take 4 MiB of VMEM.
BLOCK_ROWS = 512
BLOCK_COLS = 512


def _pick_block(logical: int, physical: int, align: int, cap: int) -> int:
    """One block dim for an axis whose input and output extents are
    ``logical`` and ``physical`` (in either order): the shared full extent
    when it is small enough, else ``cap`` or the aligned extent."""
    if logical == physical and physical <= cap:
        return physical
    return min(cap, -(-max(logical, physical) // align) * align)


def _blocks(logical: Tuple[int, int], physical: Tuple[int, int]) -> Tuple[int, int]:
    return (
        _pick_block(logical[0], physical[0], ROW_ALIGN, BLOCK_ROWS),
        _pick_block(logical[1], physical[1], COL_ALIGN, BLOCK_COLS),
    )


def _pad_kernel(x_ref, o_ref, *, m: int, n: int, bm: int, bn: int):
    i, j = pl.program_id(0), pl.program_id(1)
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    mask = (rows < m) & (cols < n)
    o_ref[...] = jnp.where(mask, x_ref[...], jnp.zeros((), o_ref.dtype))


def _strip_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("physical_shape", "interpret"))
def pad_to(
    x: jax.Array, physical_shape: Tuple[int, int], *, interpret: bool = False
) -> jax.Array:
    """Zero-pad ``x`` [m, n] up to ``physical_shape`` [mp, np] in one pass."""
    m, n = x.shape
    mp, np_ = int(physical_shape[0]), int(physical_shape[1])
    if (mp, np_) == (m, n):
        return x
    if mp < m or np_ < n:
        raise ValueError(f"cannot pad {x.shape} down to {physical_shape}")
    bm, bn = _blocks((m, n), (mp, np_))
    kern = functools.partial(_pad_kernel, m=m, n=n, bm=bm, bn=bn)
    return pl.pallas_call(
        kern,
        grid=(pl.cdiv(mp, bm), pl.cdiv(np_, bn)),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=interpret,
        name="repro_relayout_pad",
    )(x)


@functools.partial(jax.jit, static_argnames=("logical_shape", "interpret"))
def strip_to(
    x: jax.Array, logical_shape: Tuple[int, int], *, interpret: bool = False
) -> jax.Array:
    """Slice the divisibility padding off ``x`` [mp, np] down to [m, n]."""
    mp, np_ = x.shape
    m, n = int(logical_shape[0]), int(logical_shape[1])
    if (m, n) == (mp, np_):
        return x
    if m > mp or n > np_:
        raise ValueError(f"cannot strip {x.shape} up to {logical_shape}")
    bm, bn = _blocks((m, n), (mp, np_))
    return pl.pallas_call(
        _strip_kernel,
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn)),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        name="repro_relayout_strip",
    )(x)
