"""The program's spans (``al.*``): one send, run, collect and free over
in-process TCP under the profiler, the trace read back, and each span found
at its site with its stats. Spans of one request share the wire's ``rid``."""

import glob
import threading
from collections import defaultdict

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro
from repro.core.expr import content_key
from repro.core.scheduler import PlacementRequest
from repro.core.taskqueue import TaskQueue

ELEMENTAL = "repro.linalg.library:ElementalLib"


def _spans(log_dir: str) -> list:
    """(name, thread, start, end, stats) of every ``al.*`` host span."""
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        for ln, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("al."):
                    end = e.start_ns + e.duration_ns
                    out.append((e.name, (p, ln), e.start_ns, end, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Spans of one round trip through the served path, and what it moved."""
    log_dir = str(tmp_path_factory.mktemp("trace"))
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 48)).astype(np.float32)
    b = rng.standard_normal((48, 32)).astype(np.float32)
    x = rng.standard_normal((40, 24))  # float64: no shard-direct receive
    engine = repro.AlchemistEngine()
    s = repro.connect(engine, transport="tcp")
    s.register_library("elemental", ELEMENTAL)
    queue = s.session.tasks
    waited = queue.stats()["wait_ns"]
    try:
        with s.policy("eager"):
            jax.profiler.start_trace(log_dir)
            la, lb = s.send(a), s.send(b)
            c = s.run("elemental", "gemm", la, lb, cse=False)
            out = c.data()
            for h in (c, la, lb):
                h.free()
            copied = _attach_fallback(x)
            jax.profiler.stop_trace()
        np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-4)
        (snap,) = engine.stats()["sessions"].values()
        yield {
            "spans": _spans(log_dir),
            "a": a,
            "b": b,
            "c": np.asarray(out),
            "waited": (waited, queue.stats()["wait_ns"], snap["tasks"]["wait_ns"]),
            "x": x,
            "copied": copied,
        }
    finally:
        s.close()


def _attach_fallback(x: np.ndarray) -> int:
    """The store's one host copy left, the attach fallback's, on its real
    path: a second TCP session's send of ``x`` is decided as an attach, but
    the producer frees the bytes before the attach task runs, so the
    consumer publishes the array it received (an ndarray, as a float64
    takes no shard-direct receive) with a copy. Returns the store's
    ``payload_copied_bytes``."""
    engine = repro.AlchemistEngine()
    producer = repro.AlchemistContext(engine, num_workers=1, transport="tcp")
    held = producer.send(x)  # a plain send: the entry keeps no payload
    # The consumer joins the producer's worker group (one CPU device).
    share = PlacementRequest(workers=1, affinity=[content_key(x)], allow_shared=True)
    consumer = repro.connect(engine, placement=share, transport="tcp")
    try:
        gate = threading.Event()
        consumer.session.tasks.submit(gate.wait, label="gate")
        pending = consumer.send_async(x.copy())  # attach decided, task queued
        entry = engine.residents.lookup(content_key(x))
        assert entry.payload is None and consumer.session.id in entry.placements
        producer.free(held)
        gate.set()
        placed = consumer.session.resolve(pending.result(30))  # no FETCH: read in place
        np.testing.assert_allclose(np.asarray(placed.data()), x, rtol=1e-6)
        return engine.residents.stats()["payload_copied_bytes"]
    finally:
        consumer.close()
        producer.stop()


def _named(spans, name, **stats):
    return [
        sp for sp in spans if sp[0] == name and all(sp[4].get(k) == v for k, v in stats.items())
    ]


def _inside(inner, outer) -> bool:
    return inner[1] == outer[1] and outer[2] <= inner[2] and inner[3] <= outer[3]


@pytest.mark.parametrize(
    "name, stats",
    [
        ("al.host.copy", {"site": "snapshot"}),
        ("al.host.copy", {"site": "payload"}),
        ("al.store.key", {"side": "host"}),
        ("al.store.key", {"side": "staged"}),
        ("al.wire.write", {"frame": "SEND"}),
        ("al.wire.read", {}),
        ("al.server.send", {}),
        ("al.server.run", {}),
        ("al.server.collect", {}),
        ("al.server.free", {}),
        ("al.wire.recv", {}),
        ("al.device.put", {}),
        ("al.wire.fetch", {}),
        ("al.device.get", {}),
        ("al.task.send", {}),
        ("al.task.run", {}),
        ("al.task.collect", {}),
        ("al.task.free", {}),
        ("al.relayout", {"direction": "send"}),
        ("al.relayout", {"direction": "receive"}),
        ("al.routine", {"routine": "elemental.gemm"}),
    ],
)
def test_each_span_is_recorded(traced, name, stats):
    assert _named(traced["spans"], name, **stats), f"no {name} {stats}"


def test_sends_carry_their_bytes_and_rid_across_threads(traced):
    spans, a, b = traced["spans"], traced["a"], traced["b"]
    for x in (a, b):
        (write,) = _named(spans, "al.wire.write", frame="SEND", nbytes=x.nbytes)
        rid = write[4]["rid"]
        (server,) = _named(spans, "al.server.send", rid=rid)
        (recv,) = _named(spans, "al.wire.recv", rid=rid)
        assert server[1] != write[1]  # the server's connection thread
        assert recv[4]["nbytes"] == x.nbytes and _inside(recv, server)
        # The server's content key runs inside its SEND; its payload is the
        # received slabs, adopted with no host copy.
        (key,) = _named(spans, "al.store.key", side="staged", nbytes=x.nbytes)
        assert _inside(key, server)
        assert not [c for c in _named(spans, "al.host.copy", site="payload") if _inside(c, server)]
        # The client's snapshot and key run on the thread that writes.
        (snap,) = _named(spans, "al.host.copy", site="snapshot", nbytes=x.nbytes)
        (ckey,) = _named(spans, "al.store.key", side="host", nbytes=x.nbytes)
        assert snap[1] == ckey[1] == write[1] and snap[3] <= ckey[3] <= write[2]
    puts = _named(spans, "al.device.put")
    assert sorted(sp[4]["nbytes"] for sp in puts) == sorted((a.nbytes, b.nbytes))


def test_fetch_spans_share_the_rid(traced):
    spans, c = traced["spans"], traced["c"]
    (write,) = _named(spans, "al.wire.write", frame="FETCH")
    rid = write[4]["rid"]
    (fetch,) = _named(spans, "al.wire.fetch", rid=rid)
    (read,) = _named(spans, "al.wire.read", rid=rid)
    assert fetch[4]["nbytes"] == read[4]["nbytes"] == c.nbytes
    assert read[1] not in (write[1], fetch[1])  # the client's reader thread
    gets = _named(spans, "al.device.get")
    assert sum(sp[4]["nbytes"] for sp in gets) == c.nbytes
    assert all(fetch[2] <= sp[2] and sp[3] <= fetch[3] for sp in gets)


def test_every_verb_links_client_and_server_by_rid(traced):
    spans = traced["spans"]
    for frame in ("SEND", "RUN", "COLLECT", "FREE"):
        writes = _named(spans, "al.wire.write", frame=frame)
        assert writes
        for w in writes:
            assert _named(spans, f"al.server.{frame.lower()}", rid=w[4]["rid"])


def test_task_spans_hold_their_work_and_wait(traced):
    spans = traced["spans"]
    tasks = defaultdict(list)
    for sp in spans:
        if sp[0].startswith("al.task."):
            tasks[sp[0]].append(sp)
            assert sp[4]["queued_us"] >= 0
    # The round trip's tasks, plus the attach fallback's: the producer's
    # send and free, the consumer's gate and its attach.
    assert {k: len(v) for k, v in tasks.items()} == {
        "al.task.send": 2 + 1,
        "al.task.run": 1,
        "al.task.collect": 1,
        "al.task.free": 3 + 1,
        "al.task.gate": 1,
        "al.task.attach": 1,
    }
    (routine,) = _named(spans, "al.routine")
    assert any(_inside(routine, t) for t in tasks["al.task.run"])
    for rel in _named(spans, "al.relayout", direction="send"):
        assert any(_inside(rel, t) for t in tasks["al.task.send"])
    (rel,) = _named(spans, "al.relayout", direction="receive")
    assert any(_inside(rel, t) for t in tasks["al.task.collect"])


def test_payload_copy_span_carries_the_copied_bytes(traced):
    spans = traced["spans"]
    (copy,) = _named(spans, "al.host.copy", site="payload")
    assert copy[4]["nbytes"] == traced["copied"] == traced["x"].nbytes
    assert any(_inside(copy, task) for task in _named(spans, "al.task.attach"))


def test_queue_wait_counter_grows(traced):
    before, after, reported = traced["waited"]
    assert after > before
    assert reported == after  # engine.stats() carries the queue's counters


#: Task labels as the engine's queues give them, and the span each task gets.
LABELS = [
    ("send:a1", "al.task.send"),
    ("run:elemental.gemm", "al.task.run"),
    ("barrier:session-1", "al.task.barrier"),
    ("batch[3]", "al.task.batch"),
    ("", "al.task.task"),  # unlabelled: the label is the function's name, <lambda>
]


@pytest.fixture(scope="module")
def task_spans(tmp_path_factory):
    """The names of the spans of one task per label, in submission order."""
    log_dir = str(tmp_path_factory.mktemp("tasks"))
    q = TaskQueue("t")
    jax.profiler.start_trace(log_dir)
    try:
        for label, _ in LABELS:
            q.submit(lambda: None, label=label).result(5)
    finally:
        jax.profiler.stop_trace()
        q.close()
    tasks = [sp for sp in _spans(log_dir) if sp[0].startswith("al.task.")]
    return [sp[0] for sp in sorted(tasks, key=lambda sp: sp[2])]


@pytest.mark.parametrize("i", range(len(LABELS)), ids=[label or "<lambda>" for label, _ in LABELS])
def test_task_span_is_named_by_the_labels_leading_word(task_spans, i):
    assert len(task_spans) == len(LABELS)
    assert task_spans[i] == LABELS[i][1]
