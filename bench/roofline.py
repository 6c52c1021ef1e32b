"""Peaks by device kind, and the least time a routine's logical problem needs.

The operations and bytes are those of the problem as stated, not of the
implementation: a routine that reads its input several times, pads it, or
copies it into another layout does more work than counted here, so its share
can only rise as that waste is taken out, and cannot pass 100%.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
F32 = 4  # bytes per stored element: every configuration stores float32


def peak(kind: str) -> dict:
    """The peaks of ``kind`` (``device_kind`` as JAX reports it)."""
    with open(PEAKS_FILE) as f:
        kinds = json.load(f)["kinds"]
    if kind not in kinds:
        raise KeyError(f"no peaks for device kind {kind!r}: add it to peaks.json with its source")
    return kinds[kind]


def truncated_svd_counts(m: int, n: int, k: int) -> tuple[float, float]:
    """(FLOPs, bytes) of a rank-k SVD of an m x n matrix: one pass over A that
    multiplies it by k vectors, one read of A, and the writes of U, s and V."""
    return 2.0 * m * n * k, F32 * (m * n + m * k + k + n * k)


def gemm_counts(m: int, k: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of C[m, n] = A[m, k] @ B[k, n]: A and B read once, C
    written once."""
    return 2.0 * m * k * n, F32 * (m * k + k * n + m * n)


def least_time_s(flops: float, nbytes: float, kind: str) -> float:
    """The larger of FLOPs over the bf16 peak and bytes over HBM bandwidth.

    No float32 peak of the MXU is published, so the bf16 peak stands for it:
    a float32 routine is held to what the chip could do in one bf16 pass.
    """
    p = peak(kind)
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])


def share_pct(
    flops: float, nbytes: float, kind: str, busy_s_per_job: float, chips: int = 1
) -> float:
    """Least time over the device's busy time per job, in percent. On several
    chips the problem's least time is taken at ``chips`` times the peaks, and
    ``busy_s_per_job`` is the busy time per chip."""
    if busy_s_per_job <= 0:
        raise ValueError("a roofline share needs device time")
    return 100.0 * (least_time_s(flops, nbytes, kind) / chips) / busy_s_per_job
