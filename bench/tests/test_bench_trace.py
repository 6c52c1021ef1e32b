"""The reduction from a profiler trace to device busy and idle time, per-op
time, collective time and idle time by host activity: on intervals, and on
small traces recorded from short traced windows: of the GEMM cells (on the CPU
backend at a small size, where the ops run on host threads, and on one TPU
v5e), and of the SVD on a 2 x 2 group of TPU v5e chips."""

import glob
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


def test_union_merges_overlaps_and_touching():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == [(0, 4), (5, 7), (8, 9)]
    assert trace.union([]) == []


def test_gaps_are_the_complement_inside_the_window():
    busy = [(2, 4), (6, 7)]
    assert trace.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert trace.gaps([(0, 10)], 0, 10) == []
    assert trace.gaps([], 3, 5) == [(3, 5)]


def test_clip_keeps_what_lies_in_the_window():
    assert trace.clip([(0, 3), (4, 6), (9, 12)], 2, 10) == [(2, 3), (4, 6), (9, 10)]
    assert trace.clip([(0, 1)], 2, 10) == []


@pytest.mark.parametrize(
    "text, name",
    [
        ("%while.24 = (s32[], f32[10000]{0:T(1024)}) while(%tuple.175), body=%b", "while.24"),
        ("%repro_tiled_matmul.1 = f32[8,8]{1,0} custom-call(%pad.3, %pad.4)", "repro_tiled_matmul.1"),
        ("dot_general.3", "dot_general.3"),
    ],
)
def test_op_name_drops_the_hlo_text(text, name):
    assert trace.op_name(text) == name


def test_idle_time_goes_to_the_verb_the_host_was_in():
    ops = [("dot", 10, 20), ("dot", 15, 30), ("norm", 50, 60), ("late", 95, 120)]
    verbs = [("bench.run", 0, 40), ("bench.collect", 40, 55), ("bench.free", 70, 80)]
    got = trace.summarize((0, 100), verbs, [ops])
    assert got.window_s == pytest.approx(100e-9) and got.busy_s == pytest.approx(35e-9)
    assert got.op_s == pytest.approx({"dot": 25e-9, "norm": 10e-9, "late": 5e-9})
    assert got.idle_s == pytest.approx(
        {"bench.run": 20e-9, "bench.collect": 10e-9, "bench.free": 10e-9, "bench.between": 25e-9}
    )
    # Each verb's own time: its span less the device's busy time inside it.
    assert got.verb_n == {"bench.run": 1, "bench.collect": 1, "bench.free": 1}
    assert got.verb_host_s == pytest.approx(
        {"bench.run": 20e-9, "bench.collect": 10e-9, "bench.free": 10e-9}
    )


def test_overlap_counts_the_busy_part_of_each_span():
    busy = [(2, 4), (6, 9), (12, 13)]
    assert trace.overlap([(0, 3), (3, 7), (8, 12), (14, 15)], busy) == [1, 2, 1, 0]
    assert trace.overlap([(0, 20)], busy) == [6]


def test_busy_time_is_averaged_over_chips():
    got = trace.summarize((0, 100), [], [[("a", 0, 50)], [("b", 0, 100)]])
    assert got.busy_s == pytest.approx(75e-9)
    assert got.idle_s == pytest.approx({"bench.between": 25e-9})


@pytest.fixture(scope="module", params=RECORDED, ids=os.path.basename)
def recorded(request):
    return trace.reduce(request.param)


def test_cpu_trace_reduces_to_its_recorded_numbers():
    got = trace.reduce(os.path.join(DATA, "gemm10k_resident_cpu.xplane.pb"))
    assert got.busy_s == pytest.approx(0.009498087, abs=1e-9)
    assert got.window_s == pytest.approx(0.051772656, abs=1e-9)
    assert got.top_ops(1)[0][0] == "dot_general.3"
    assert got.idle_s["bench.run"] == pytest.approx(0.031898197, abs=1e-9)


def test_tpu_trace_reduces_to_its_recorded_numbers():
    # 14 jobs of gemm10k.resident on a TPU v5e: ops on the device plane's
    # "XLA Ops" line, named without their HLO text.
    got = trace.reduce(os.path.join(DATA, "gemm10k_resident_tpu.xplane.pb"))
    assert got.busy_s == pytest.approx(0.378967265, abs=1e-9)
    assert got.window_s == pytest.approx(0.517689134, abs=1e-9)
    assert got.top_ops(1)[0] == ("repro_tiled_matmul.1", pytest.approx(0.320539099, abs=1e-9))
    assert got.verb_n == {"bench.run": 28, "bench.collect": 14, "bench.free": 14}
    assert got.idle_s["bench.run"] == pytest.approx(0.137487084, abs=1e-9)
    assert got.chips == 1 and got.collective_s == 0
    assert trace.reduce(os.path.join(DATA, "gemm10k_resident_tpu.xplane.pb"), chips=1) == got


def test_four_chip_trace_reads_each_chip_and_its_collectives():
    # One job of the truncated SVD at 20,000 x 10,000 on a 2 x 2 group of TPU
    # v5e chips (the svd625k configuration at a small m), trimmed to what the
    # reduction reads: each chip's "XLA Ops" line and the harness's spans.
    path = os.path.join(DATA, "svd20k_grid2x2_tpu.xplane.pb")
    got = trace.reduce(path)
    assert got.chips == 4
    assert got.busy_s == pytest.approx(0.01714686875, abs=1e-9)
    assert got.collective_s == pytest.approx(0.00063842275, abs=1e-9)
    assert got.window_s == pytest.approx(0.041989769, abs=1e-9)
    assert got.top_ops(1)[0] == ("while.30", pytest.approx(0.068095601, abs=1e-9))
    assert got.verb_n == {"bench.run": 1, "bench.collect": 3, "bench.free": 3}
    assert trace.reduce(path, chips=4) == got
    one = trace.reduce(path, chips=1)
    assert one.chips == 1 and 0 < one.collective_s < one.busy_s


def test_recorded_trace_busy_and_idle_add_up(recorded):
    assert 0 < recorded.busy_s < recorded.window_s
    idle = sum(recorded.idle_s.values())
    assert idle == pytest.approx(recorded.window_s - recorded.busy_s, rel=1e-9, abs=1e-9)
    assert set(recorded.idle_s) <= {
        "bench.send", "bench.run", "bench.collect", "bench.free", "bench.between"
    }


def test_recorded_trace_ops(recorded):
    ops = recorded.top_ops()
    assert 0 < len(ops) <= 10
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    # The op times, summed, cover at least the busy union (ops may overlap).
    assert sum(recorded.op_s.values()) >= recorded.busy_s * (1 - 1e-9)


@pytest.mark.parametrize(
    "ops, intervals",
    [
        (
            [("all-reduce.4", 10, 20), ("fusion.1", 20, 30), ("all-gather", 40, 45)],
            [(10, 20), (40, 45)],
        ),
        # An async pair counts once, from its start to its done, ops between
        # them included; a done closes the start of its own number.
        (
            [
                ("all-reduce-start.1", 0, 2),
                ("fusion", 2, 8),
                ("all-reduce-start.2", 3, 4),
                ("all-reduce-done.1", 8, 9),
                ("all-reduce-done.2", 9, 12),
            ],
            [(0, 9), (3, 12)],
        ),
        (
            [
                ("all-gather-start.1", 0, 2),
                ("all-gather-start.2", 3, 4),
                ("all-gather-done.2", 5, 6),
                ("all-gather-done.1", 8, 9),
                ("all-gather-done.3", 10, 11),
            ],
            [(3, 6), (0, 9)],
        ),
        ([("reduce-scatter.2", 5, 6), ("collective-permute-done", 7, 8), ("all-to-all", 9, 10)],
         [(5, 6), (9, 10)]),
        ([("dot", 0, 5), ("while.3", 0, 9)], []),
    ],
)
def test_collective_intervals(ops, intervals):
    assert trace.collective_intervals(ops) == intervals


def test_collective_time_is_the_union_per_chip_averaged_over_chips():
    chips = [
        [("fusion", 0, 40), ("all-reduce.1", 40, 50), ("all-reduce-start", 60, 61),
         ("fusion.2", 61, 70), ("all-reduce-done", 70, 72), ("all-gather", 95, 110)],
        [("fusion", 0, 30), ("all-reduce.1", 30, 50)],
    ]
    got = trace.summarize((0, 100), [], chips)
    assert got.chips == 2
    # chip 0: 10 + 12 + 5 (clipped to the window); chip 1: 20.
    assert got.collective_s == pytest.approx((27e-9 + 20e-9) / 2)
    assert got.busy_s == pytest.approx((67e-9 + 50e-9) / 2)


def test_an_async_pair_over_an_idle_stretch_counts_only_its_busy_time():
    # The pair is in flight from 0 to 61, but the chip runs ops for 12 of it.
    chip = [("all-reduce-start.3", 0, 1), ("fusion", 50, 60), ("all-reduce-done.3", 60, 61)]
    got = trace.summarize((0, 100), [], [chip])
    assert got.busy_s == pytest.approx(12e-9)
    assert got.collective_s == pytest.approx(12e-9)


def test_a_trace_of_another_number_of_chips_than_the_cell_is_refused():
    with pytest.raises(ValueError, match="the ops of 1 chips, the cell has 4"):
        trace.reduce(os.path.join(DATA, "gemm10k_resident_tpu.xplane.pb"), chips=4)
    # The CPU backend's ops have no device plane: they stand for every chip.
    assert trace.reduce(os.path.join(DATA, "gemm10k_resident_cpu.xplane.pb"), chips=4).chips == 1


def test_collective_share_reads_each_chips_collective_time_over_its_busy_time():
    from types import SimpleNamespace

    from bench import harness

    read = harness.load_reader("collective_pct.svd")
    summary = trace.Summary(window_s=1.0, busy_s=0.5, chips=4, collective_s=0.1)
    assert read(SimpleNamespace(trace=summary)) == pytest.approx(20.0)
    assert read(SimpleNamespace(trace=None)) is None
    assert read(SimpleNamespace(trace=trace.Summary(window_s=1.0, busy_s=0.0))) is None
