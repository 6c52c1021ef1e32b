"""The reduction from a profiler trace to device busy and idle time, per-op
time and idle time by host activity: on intervals, and on small traces
recorded from short traced windows of the gemm10k.resident cell (on the CPU
backend at a small size, where the ops run on host threads)."""

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
    return trace.reduce(request.param, chips=1)


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
