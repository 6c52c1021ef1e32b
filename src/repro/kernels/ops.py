"""Dispatching wrappers — the public kernel API the rest of the framework uses.

On TPU, calls lower to the Pallas kernels; elsewhere (CPU hosts, unit
tests) they run the pure-jnp oracles in :mod:`repro.kernels.ref`. Set
``REPRO_FORCE_PALLAS=interpret`` to exercise the kernel bodies on CPU via
interpret mode (used by the kernel test suite). A kernel that cannot lower
raises: there is no silent fallback to the oracles on TPU.

The dispatch is deliberately *per-call-site static* (a module-level backend
probe), so jitted programs never trace both paths.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax

from repro.kernels import flash_attention as _flash
from repro.kernels import matmul as _matmul
from repro.kernels import ref as _ref
from repro.kernels import relayout_pad as _relayout_pad
from repro.kernels import ssd_scan as _ssd

_FORCE = os.environ.get("REPRO_FORCE_PALLAS", "").lower()


def backend() -> str:
    if _FORCE == "interpret":
        return "pallas-interpret"
    if _FORCE in ("1", "true", "tpu"):
        return "pallas"
    return "pallas" if jax.default_backend() == "tpu" else "ref"


_BACKEND = backend()

# Dry-run cost-variant mode: "real" (default), "stub" (O(L·D) stand-in so the
# cost fit isolates non-attention work; see repro.roofline.attention_model).
ATTENTION_MODE = "real"


def use_pallas() -> bool:
    return _BACKEND.startswith("pallas")


def _interp() -> bool:
    return _BACKEND == "pallas-interpret"


def matmul(
    a: jax.Array, b: jax.Array, *, out_dtype=None, block: Optional[Tuple[int, int, int]] = None
) -> jax.Array:
    """Local (per-device) GEMM with f32 accumulation."""
    if use_pallas():
        bm, bn, bk = block or (_matmul.DEFAULT_BM, _matmul.DEFAULT_BN, _matmul.DEFAULT_BK)
        return _matmul.matmul(
            a, b, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype, interpret=_interp()
        )
    return _ref.matmul(a, b, out_dtype=out_dtype)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """GQA scaled-dot-product attention [B, Hq, Lq, D] x [B, Hkv, Lk, D]."""
    if ATTENTION_MODE == "stub":
        return _ref.attention_stub(q, k, v)
    if use_pallas():
        lq, lk = q.shape[2], k.shape[2]
        bq, bk = block or (_flash.DEFAULT_BQ, _flash.DEFAULT_BK)
        # shrink blocks to legal divisors for small/ragged shapes
        while lq % min(bq, lq):
            bq //= 2
        while lk % min(bk, lk):
            bk //= 2
        return _flash.flash_attention(
            q, k, v,
            causal=causal, window=window, scale=scale, q_offset=q_offset,
            bq=bq, bk=bk, interpret=_interp(),
        )
    lq, lk = q.shape[2], k.shape[2]
    if lq >= 2048 and lq * lk >= (1 << 22):
        # flash-structured streaming program: bounded memory, kernel-like
        # HBM traffic in the dry-run's memory analysis
        return _ref.attention_chunked(
            q, k, v, causal=causal, window=window, scale=scale, q_offset=q_offset
        )
    return _ref.attention(
        q, k, v, causal=causal, window=window, scale=scale, q_offset=q_offset
    )


def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    a: jax.Array,
    b_mat: jax.Array,
    c_mat: jax.Array,
    *,
    init_state: Optional[jax.Array] = None,
    chunk: int = 64,
) -> Tuple[jax.Array, jax.Array]:
    """Mamba2 SSD over a sequence; returns (y, final_state)."""
    if use_pallas():
        return _ssd.ssd_scan(
            x, dt, a, b_mat, c_mat, init_state=init_state, chunk=chunk,
            interpret=_interp(),
        )
    if x.shape[1] % max(min(chunk, x.shape[1]), 1) == 0 and x.shape[1] >= chunk:
        # chunked oracle: same math as the kernel, parallel-friendly HLO
        return _ref.ssd_chunked(x, dt, a, b_mat, c_mat, chunk=chunk, init_state=init_state)
    return _ref.ssd_scan(x, dt, a, b_mat, c_mat, init_state=init_state)


def _fusable(x) -> bool:
    """Pallas pad/strip take one device's buffer: numpy hosts and
    single-device jax arrays qualify; sharded arrays take the jnp path."""
    if isinstance(x, jax.Array):
        return len(x.sharding.device_set) == 1
    return True  # numpy / python buffers: pallas_call will device_put them


def pad_to(x, physical_shape: Tuple[int, int]):
    """Pad ``x`` up to the layout's physical shape.

    Returns ``(padded, path)`` where ``path`` is the backend that actually
    ran: "pallas" / "pallas-interpret" (fused kernel) or "ref" (jnp.pad).
    The plan cache records the path so benchmarks can attribute fusion.
    """
    if use_pallas() and _fusable(x):
        return _relayout_pad.pad_to(x, tuple(physical_shape), interpret=_interp()), _BACKEND
    return _ref.pad_to(x, tuple(physical_shape)), "ref"


def strip_to(x, logical_shape: Tuple[int, int]):
    """Strip divisibility padding down to the logical shape.

    Returns ``(stripped, path)`` — same contract as :func:`pad_to`.
    """
    if use_pallas() and _fusable(x):
        return _relayout_pad.strip_to(x, tuple(logical_shape), interpret=_interp()), _BACKEND
    return _ref.strip_to(x, tuple(logical_shape)), "ref"


def ssd_step(
    x: jax.Array,      # [B, 1, H, P] single token
    dt: jax.Array,     # [B, 1, H]
    a: jax.Array,      # [H]
    b_mat: jax.Array,  # [B, 1, G, N]
    c_mat: jax.Array,  # [B, 1, G, N]
    state: jax.Array,  # [B, H, P, N]
) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSD recurrence for decode (no kernel needed: O(1) work)."""
    return _ref.ssd_scan(x, dt, a, b_mat, c_mat, init_state=state)
