"""Tiled matmul Pallas kernel — the engine's local GEMM.

The paper's compute hot spot is dense GEMM (Elemental's ``Gemm`` wrapped via
the ALI, §4.1). On TPU the distributed layer (SUMMA, :mod:`repro.linalg.gemm`)
reduces to *local* GEMMs per device; this kernel is that local GEMM, tiled
for VMEM with an f32 accumulator held in scratch across the K-loop.

Tiling notes (v5e): MXU is a 128x128 systolic array — block dims are
multiples of 128 in production (defaults below); the K grid dimension is
innermost so the accumulator tile stays resident in VMEM while A/B tiles
stream HBM→VMEM. VMEM working set = bm*bk + bk*bn + bm*bn(f32)
≈ (512·512·2)·2 + 512·512·4 ≈ 2.1 MiB at defaults — comfortably inside the
~16 MiB/core budget, leaving room for double-buffering.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Production block sizes (MXU-aligned). Tests sweep smaller ones.
DEFAULT_BM = 512
DEFAULT_BN = 512
DEFAULT_BK = 512


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    """One (i, j, k) grid step: acc += A[i,k] @ B[k,j]; flush at last k."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x: jax.Array, mult: Tuple[int, int]) -> jax.Array:
    m, n = x.shape
    pm, pn = (-m) % mult[0], (-n) % mult[1]
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """C[m,n] = A[m,k] @ B[k,n] with f32 accumulation.

    Inputs are zero-padded up to block multiples (zero padding is exact for
    matmul); the result is sliced back.
    """
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"matmul expects 2D operands, got {a.shape} @ {b.shape}")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    out_dtype = out_dtype or a.dtype
    m, kdim = a.shape
    _, n = b.shape

    bm_, bn_, bk_ = min(bm, max(m, 1)), min(bn, max(n, 1)), min(bk, max(kdim, 1))
    with jax.named_scope("gemm.pad"):
        ap = _pad_to(a, (bm_, bk_))
        bp = _pad_to(b, (bk_, bn_))
    mp, kp = ap.shape
    _, np_ = bp.shape

    with jax.named_scope("gemm.kernel"):
        out = pl.pallas_call(
            _matmul_kernel,
            grid=(mp // bm_, np_ // bn_, kp // bk_),
            in_specs=[
                pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
            interpret=interpret,
            name="repro_tiled_matmul",
        )(ap, bp)
    with jax.named_scope("gemm.strip"):
        return out[:m, :n]


def vmem_bytes(bm: int, bn: int, bk: int, dtype=jnp.bfloat16) -> int:
    """Working-set estimate used by block-size selection and DESIGN notes."""
    itm = jnp.dtype(dtype).itemsize
    return bm * bk * itm + bk * bn * itm + bm * bn * 4
