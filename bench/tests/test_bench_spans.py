"""The program's spans in a trace (bench/spans.py): self times, idle time put
down to the span open at each instant, device ops' self time, and the
quantities of the program's layers; on synthetic spans and on the recorded
traces beside bench/tests/test_bench_trace.py's."""

import glob
import os
from types import SimpleNamespace

import pytest

from bench import spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


def span(name, thread, start, end, **stats):
    return spans.Span(name, thread, start, end, stats)


def test_nest_takes_each_interval_less_its_direct_children():
    # outer [0, 100) holds [10, 40) (which holds [20, 30)) and [50, 60);
    # [200, 210) stands alone.
    items = [(0, 100), (10, 40), (20, 30), (50, 60), (200, 210)]
    assert spans.nest(items) == [60, 20, 10, 10, 10]
    assert spans.nest([]) == []


def test_self_time_counts_children_on_the_same_thread_only():
    program = [
        span("al.server.send", "server", 0, 100),
        span("al.wire.recv", "server", 10, 70),
        span("al.device.put", "ring", 20, 60),  # another thread: not a child
        span("al.store.key", "server", 80, 95),
    ]
    got = spans.with_self_times(program)
    assert [sp.self_ns for sp in got] == [25, 60, 40, 15]


def test_winner_is_the_span_started_last_across_threads():
    program = [
        span("al.server.send", "server", 0, 100),
        span("al.wire.recv", "server", 10, 70),
        span("al.device.put", "ring", 20, 40),
        span("al.task.send", "worker", 90, 150),
    ]
    assert spans.winners(program) == [
        (0, 10, "al.server.send"),
        (10, 20, "al.wire.recv"),
        (20, 40, "al.device.put"),
        (40, 70, "al.wire.recv"),
        (70, 90, "al.server.send"),
        (90, 150, "al.task.send"),
    ]


def test_idle_time_goes_to_the_program_span_open_in_it():
    # The device is busy [0, 10) and [95, 100); the rest of the window is idle.
    ops = [("dot", 0, 10), ("norm", 95, 100)]
    verbs = [("bench.send", 5, 60), ("bench.run", 70, 100)]
    program = [
        span("al.wire.recv", "server", 12, 30),
        span("al.device.put", "ring", 20, 25),
        span("al.task.send", "worker", 40, 50),
        span("al.task.run", "worker", 60, 80),  # starts in the harness's own time
    ]
    idle = spans.idle_by_span((0, 100), verbs, program, [ops])
    ns = {name: pytest.approx(s * 1e9) for name, s in idle.items()}
    assert ns == {
        "bench.send/al.wire.recv": 13,
        "bench.send/al.device.put": 5,
        "bench.send/al.task.send": 10,
        "bench.send": 22,  # no program span open: [10, 12) [30, 40) [50, 60)
        "bench.run/al.task.run": 10,
        "bench.run": 15,
        "bench.between": 10,  # unchanged, although al.task.run is open in it
    }
    # Summed by verb, the attribution of bench/trace.py.
    old = trace.summarize((0, 100), verbs, [ops]).idle_s
    assert spans.by_verb(idle) == pytest.approx(old)


def test_idle_by_span_is_a_mean_over_chips():
    program = [span("al.wire.fetch", "fetch", 0, 100)]
    chips = [[("a", 0, 50)], [("b", 0, 100)]]
    idle = spans.idle_by_span((0, 100), [("bench.collect", 0, 100)], program, chips)
    assert idle == pytest.approx(
        {"bench.collect/al.wire.fetch": 25e-9, "bench.collect": 0.0, "bench.between": 0.0}
    )


def test_op_self_time_takes_a_loop_less_its_body():
    # A while loop enclosing two fusions on one line, as on the TPU's XLA Ops.
    ops = [
        ("while.24", 0, 0, 100),
        ("fusion.1", 0, 10, 50),
        ("fusion.2", 0, 50, 90),
        ("copy.3", 0, 120, 130),
    ]
    got = spans.op_self_s([ops], (0, 125))
    want = {"while.24": 20e-9, "fusion.1": 40e-9, "fusion.2": 40e-9, "copy.3": 5e-9}
    assert got == pytest.approx(want)


def _event(name, start, dur, **stats):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def _plane(name, *lines):
    return SimpleNamespace(name=name, lines=[SimpleNamespace(name=n, events=e) for n, e in lines])


def test_device_ops_are_those_of_the_chips_that_ran_some():
    pd = SimpleNamespace(
        planes=[
            _plane("/host:CPU", ("python", [_event("fusion.9", 0, 5, hlo_op="fusion.9")])),
            _plane("/device:TPU:0", ("Steps", []), ("XLA Ops", [_event("%dot.1 = f32 dot", 0, 7)])),
            _plane("/device:TPU:1", ("XLA Ops", [])),  # a chip the cell left idle
        ]
    )
    assert spans.device_ops(pd) == [[("dot.1", 1, 0, 7)]]


def test_device_ops_fall_back_to_the_hosts_ops_without_a_chip():
    pd = SimpleNamespace(
        planes=[
            _plane("/host:CPU", ("main", [_event("bench.run", 0, 9)])),
            _plane("/host:CPU", ("worker", [_event("fusion.9", 2, 5, hlo_op="fusion.9")])),
        ]
    )
    assert spans.device_ops(pd) == [[("fusion.9", (1, 0), 2, 7)]]


SYNTHETIC = [
    span("al.wire.recv", "server", 0, 2e9, nbytes=4 * 10**9, rid=4),
    span("al.wire.recv", "server", 3e9, 5e9, nbytes=2 * 10**9, rid=5),
    span("al.store.key", "client", 0, 4e9, nbytes=6 * 10**9, side="host"),
    span("al.store.key", "server", 0, 2e9, nbytes=6 * 10**9, side="staged"),
    span("al.device.put", "ring", 0, 1e9, nbytes=6 * 10**9),
    span("al.wire.fetch", "fetch", 0, 4e9, nbytes=2 * 10**9, rid=9),
    span("al.task.send", "worker", 0, 1, queued_us=100.0),
    span("al.task.run", "worker", 1, 2, queued_us=300.0),
    span("al.routine", "worker", 1, 2, routine="elemental.gemm"),
]


@pytest.mark.parametrize(
    "name, value",
    [
        ("wire.recv_GBps", 1.5),
        ("store.key_GBps", 2.0),
        ("store.hashed_per_sent", 2.0),
        ("device.put_GBps", 6.0),
        ("wire.fetch_GBps", 0.5),
        ("taskqueue.wait_ms", 0.2),
    ],
)
def test_quantities_of_the_program_layers(name, value):
    reduced = spans.Reduced((0, 1), [], SYNTHETIC, {}, {})
    assert spans.report(reduced)["quantities"][name] == pytest.approx(value)


def test_quantities_read_nothing_where_no_span_is():
    assert spans.gbps(SYNTHETIC, "al.nothing") is None
    assert spans.hashed_per_sent([]) is None and spans.wait_ms([]) is None


def test_coverage_is_the_union_over_threads():
    program = [
        span("al.wire.recv", "server", 10, 60),
        span("al.device.put", "ring", 40, 80),
        span("al.wire.write", "client", 0, 100),  # not on the send path's list
    ]
    assert spans.coverage((0, 100), program) == pytest.approx(0.7)


@pytest.fixture(scope="module", params=RECORDED, ids=os.path.basename)
def recorded(request):
    return request.param, spans.reduce(request.param), trace.reduce(request.param)


def test_recorded_idle_sums_by_verb_to_the_old_attribution(recorded):
    _, new, old = recorded
    assert spans.by_verb(new.idle_s) == pytest.approx(old.idle_s, rel=1e-9, abs=1e-12)


def test_recorded_op_self_times_lie_between_busy_and_summed_op_time(recorded):
    _, new, old = recorded
    assert set(new.op_self_s) == set(old.op_s)
    assert sum(new.op_self_s.values()) <= sum(old.op_s.values()) * (1 + 1e-9)
    assert sum(new.op_self_s.values()) >= old.busy_s * (1 - 1e-9)


def test_tpu_resident_trace_self_times_match_busy():
    # One chip, one line of ops: self times add up to the busy union exactly.
    path = os.path.join(DATA, "gemm10k_resident_tpu.xplane.pb")
    new, old = spans.reduce(path), trace.reduce(path)
    assert sum(new.op_self_s.values()) == pytest.approx(old.busy_s, rel=1e-9)
    assert new.spans == []  # recorded before the program had spans


ROUNDTRIP = os.path.join(DATA, "gemm10k_roundtrip_tpu.xplane.pb")


def test_tpu_roundtrip_trace_reduces_to_its_recorded_numbers():
    # One gemm10k.roundtrip job on a TPU v5e, with the program's spans.
    got = trace.reduce(ROUNDTRIP)
    assert got.busy_s == pytest.approx(0.026561555, abs=1e-9)
    assert got.window_s == pytest.approx(4.901846277, abs=1e-9)
    assert got.top_ops(1)[0] == ("repro_tiled_matmul.1", pytest.approx(0.02290458, abs=1e-9))
    assert got.verb_n == {"bench.send": 2, "bench.run": 1, "bench.collect": 1, "bench.free": 3}
    assert got.idle_s["bench.send"] == pytest.approx(4.045769456, abs=1e-9)
    assert got.chips == 1 and got.collective_s == 0


@pytest.mark.parametrize(
    "name, count",
    [
        ("al.host.copy", 4),
        ("al.store.key", 4),
        ("al.wire.write", 8),
        ("al.wire.read", 1),
        ("al.server.send", 2),
        ("al.server.run", 1),
        ("al.server.collect", 1),
        ("al.server.free", 3),
        ("al.wire.recv", 2),
        ("al.device.put", 2),
        ("al.wire.fetch", 1),
        ("al.device.get", 1),
        ("al.task.send", 2),
        ("al.task.run", 1),
        ("al.task.collect", 1),
        ("al.task.free", 3),
        ("al.relayout", 3),
        ("al.routine", 1),
    ],
)
def test_tpu_roundtrip_trace_holds_each_span(name, count):
    found = [sp for sp in spans.reduce(ROUNDTRIP).spans if sp.name == name]
    assert len(found) == count
    wanted = {
        "al.wire.write": "rid",
        "al.wire.read": "rid",
        "al.server.send": "rid",
        "al.wire.recv": "nbytes",
        "al.wire.fetch": "nbytes",
        "al.device.put": "nbytes",
        "al.task.send": "queued_us",
        "al.routine": "routine",
    }
    if name in wanted:
        assert all(wanted[name] in sp.stats for sp in found)


def test_tpu_roundtrip_trace_shows_where_the_send_goes():
    got = spans.reduce(ROUNDTRIP)
    sends = [(s, e) for name, s, e in got.verbs if name == "bench.send"]
    assert len(sends) == 2
    assert all(spans.coverage(iv, got.spans) >= 0.99 for iv in sends)
    for verb in ("bench.send", "bench.collect"):
        total = sum(s for name, s in got.idle_s.items() if name.split("/")[0] == verb)
        named = sum(s for name, s in got.idle_s.items() if name.startswith(verb + "/"))
        assert named >= 0.99 * total
    assert got.idle_s["bench.send/al.store.key"] == pytest.approx(2.028008096, abs=1e-9)
    assert got.idle_s["bench.send/al.host.copy"] == pytest.approx(1.642784006, abs=1e-9)
    q = spans.report(got)["quantities"]
    assert q["store.hashed_per_sent"] == 2.0  # the client's key and the server's, per byte
    assert q["wire.recv_GBps"] == pytest.approx(2.826600380, rel=1e-6)
    assert q["store.key_GBps"] == pytest.approx(0.788951486, rel=1e-6)
    assert q["device.put_GBps"] == pytest.approx(9.607453616, rel=1e-6)
    assert q["wire.fetch_GBps"] == pytest.approx(0.536855953, rel=1e-6)
