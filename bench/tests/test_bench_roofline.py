"""The peaks table and the logical problem's operations and bytes."""

import json

import pytest

from bench import roofline

V5E = "TPU v5 lite"


def test_peaks_name_their_source():
    with open(roofline.PEAKS_FILE) as f:
        table = json.load(f)
    assert "cloud.google.com/tpu/docs/v5e" in table["source"]
    assert table["kinds"][V5E]["bf16_flops_per_s"] == 197e12
    assert table["kinds"][V5E]["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peak("cpu")
    with pytest.raises(KeyError):
        roofline.least_time_s(1.0, 1.0, "TPU v9 imaginary")


def test_svd_counts_at_the_configured_shape():
    flops, nbytes = roofline.truncated_svd_counts(100_000, 10_000, 20)
    assert flops == 2 * 100_000 * 10_000 * 20
    assert nbytes == 4 * (100_000 * 10_000 + 100_000 * 20 + 20 + 10_000 * 20)
    # HBM-bound: one read of the 4 GB matrix at 819 GB/s, about 4.9 ms.
    assert roofline.least_time_s(flops, nbytes, V5E) == pytest.approx(nbytes / 819e9)
    assert 4.8e-3 < roofline.least_time_s(flops, nbytes, V5E) < 5.0e-3


def test_gemm_counts_at_the_configured_shape():
    flops, nbytes = roofline.gemm_counts(10_000, 10_000, 10_000)
    assert flops == 2e12 and nbytes == 4 * 3e8
    # Compute-bound: 2 TFLOP at the bf16 peak, about 10.2 ms.
    assert roofline.least_time_s(flops, nbytes, V5E) == pytest.approx(2e12 / 197e12)


def test_a_share_for_a_described_kind_needs_no_chip():
    flops, nbytes = roofline.gemm_counts(10_000, 10_000, 10_000)
    least = roofline.least_time_s(flops, nbytes, V5E)
    assert roofline.share_pct(flops, nbytes, V5E, 2 * least) == pytest.approx(50.0)
    with pytest.raises(ValueError):
        roofline.share_pct(flops, nbytes, V5E, 0.0)


def test_a_problem_shared_by_four_chips_reads_the_same_share():
    # Four chips hold four times the peaks, and each is busy for a quarter
    # of the time one chip would be: the share is the same.
    flops, nbytes = roofline.truncated_svd_counts(625_000, 10_000, 20)
    least = roofline.least_time_s(flops, nbytes, V5E)
    one = roofline.share_pct(flops, nbytes, V5E, 3 * least)
    assert one == pytest.approx(100 / 3)
    assert roofline.share_pct(flops, nbytes, V5E, 3 * least / 4, chips=4) == pytest.approx(one)
    # At one chip the reading is the one the formula always gave, bit for bit.
    assert roofline.share_pct(flops, nbytes, V5E, 0.3, chips=1) == 100.0 * least / 0.3
