"""The program's own spans (``al.*``) in a profiler trace, and what they say.

The program marks its layer boundaries with ``jax.profiler.TraceAnnotation``
spans named ``al.<layer>.<what>``, with stats such as ``nbytes``, ``rid`` or
``queued_us`` (see PERF.md §3). They land in the same ``.xplane.pb`` as the
device's operations, on the same clock. This module reads them beside
:mod:`bench.trace`, whose reduction and metrics it leaves as they are:

- :func:`host_spans`: every ``al.*`` span on every host thread, with its
  stats and its self time (its duration less the ``al.*`` spans it encloses
  on its thread).
- :func:`idle_by_span`: the device's idle time as :func:`bench.trace.summarize`
  puts it down to the harness's verbs, with each verb's share split further
  by the program span open at each instant: the innermost on its thread, and
  across threads the one that started last. That share is reported as
  ``<verb>/<span>``; idle time under a verb with no program span open stays
  ``<verb>``, and ``bench.between`` is unchanged. Summed by verb, it is
  :mod:`bench.trace`'s attribution.
- :func:`op_self_s`: each device operation's self time, its duration less
  the operations it encloses on its line (a ``while`` less its body's ops).
- the quantities of the program's layers: bytes per second through a span
  (:func:`gbps`), bytes hashed per byte received (:func:`hashed_per_sent`),
  and the task queue's mean wait (:func:`wait_ms`).

Run as a script, it prints all of that for one trace as JSON::

    python3 bench/spans.py <trace.xplane.pb>
"""

from __future__ import annotations

import heapq
import os
import sys
from collections import defaultdict
from dataclasses import dataclass, field

if __package__ in (None, ""):  # run as a script: the checkout's root holds bench/
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace  # noqa: E402

PREFIX = "al."
#: The spans whose union is the send's work, from socket to resident array.
SEND_PATH = (
    "al.wire.recv",
    "al.store.key",
    "al.host.copy",
    "al.device.put",
    "al.task.send",
    "al.relayout",
)


@dataclass
class Span:
    name: str
    thread: tuple  # (plane, line) of the host thread it ran on
    start: float  # ns
    end: float  # ns
    stats: dict = field(default_factory=dict)
    self_ns: float = 0.0  # duration less the al.* spans it encloses on its thread

    @property
    def dur(self) -> float:
        return self.end - self.start


def nest(items: list) -> list:
    """For (start, end) intervals of one thread or line, each properly nested
    in or disjoint from the others: each one's length less that of the
    intervals directly inside it, in the input's order."""
    order = sorted(range(len(items)), key=lambda i: (items[i][0], -items[i][1]))
    own = [e - s for s, e in items]
    stack: list = []  # indices of the open intervals, outermost first
    for i in order:
        s, e = items[i]
        while stack and items[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= items[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def with_self_times(spans: list) -> list:
    """``spans`` with ``self_ns`` set, per thread."""
    by_thread = defaultdict(list)
    for sp in spans:
        by_thread[sp.thread].append(sp)
    for group in by_thread.values():
        for sp, own in zip(group, nest([(sp.start, sp.end) for sp in group])):
            sp.self_ns = own
    return spans


def host_spans(pd) -> list:
    """Every ``al.*`` span of the trace's host planes, with self times."""
    out = []
    for p, plane in enumerate(pd.planes):
        if plane.name.startswith("/device:"):
            continue
        for ln, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    end = e.start_ns + e.duration_ns
                    out.append(Span(e.name, (p, ln), e.start_ns, end, dict(e.stats)))
    return with_self_times(out)


def winners(spans: list) -> list:
    """The program span open at each instant, as ordered, disjoint
    (start, end, name) segments: of the spans open then, the one started
    last (on one thread, the innermost); where none is open, no segment."""
    starts = sorted(spans, key=lambda sp: sp.start)
    points = sorted({t for sp in spans for t in (sp.start, sp.end)})
    heap: list = []  # (-start, end, name): the latest start on top
    out: list = []
    nxt = 0
    for t0, t1 in zip(points, points[1:]):
        while nxt < len(starts) and starts[nxt].start <= t0:
            sp = starts[nxt]
            heapq.heappush(heap, (-sp.start, sp.end, sp.name))
            nxt += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][2]
        if out and out[-1][2] == name and out[-1][1] == t0:
            out[-1] = (out[-1][0], t1, name)
        else:
            out.append((t0, t1, name))
    return out


def split(piece: tuple, segments: list, first: int) -> tuple:
    """The parts of ``piece`` (start, end) under each of the ordered
    ``segments``, from index ``first`` on: ({name: ns}, the first segment a
    later piece can meet)."""
    s, e = piece
    while first < len(segments) and segments[first][1] <= s:
        first += 1
    parts: dict = defaultdict(float)
    i = first
    while i < len(segments) and segments[i][0] < e:
        g0, g1, name = segments[i]
        parts[name] += min(e, g1) - max(s, g0)
        i += 1
    return parts, first


def idle_by_span(window: tuple, verbs: list, spans: list, devices: list) -> dict:
    """Idle seconds by ``<verb>/<span>``, ``<verb>`` and ``bench.between``,
    mean over chips; the arguments are :func:`bench.trace.summarize`'s, with
    the ``al.*`` ``spans`` besides."""
    lo, hi = window
    verbs = sorted((s, e, name) for name, s, e in verbs if s >= lo and e <= hi)
    segments = winners([sp for sp in spans if sp.end > lo and sp.start < hi])
    idle: dict = defaultdict(float)
    for evs in devices:
        busy = trace.union([iv for _, s, e in evs for iv in trace.clip([(s, e)], lo, hi)])
        first = seg = 0
        for g0, g1 in trace.gaps(busy, lo, hi):
            while first < len(verbs) and verbs[first][1] <= g0:
                first += 1
            covered, i = 0.0, first
            while i < len(verbs) and verbs[i][0] < g1:
                s, e, verb = verbs[i]
                a, b = max(s, g0), min(e, g1)
                parts, seg = split((a, b), segments, seg)
                for name, ns in parts.items():
                    idle[f"{verb}/{name}"] += ns / 1e9 / len(devices)
                idle[verb] += ((b - a) - sum(parts.values())) / 1e9 / len(devices)
                covered += b - a
                i += 1
            idle[trace.BETWEEN] += ((g1 - g0) - covered) / 1e9 / len(devices)
    return dict(idle)


def by_verb(idle: dict) -> dict:
    """``idle_by_span``'s seconds summed by verb: :mod:`bench.trace`'s
    attribution."""
    out: dict = defaultdict(float)
    for name, s in idle.items():
        out[name.split("/", 1)[0]] += s
    return dict(out)


def device_ops(pd) -> list:
    """Per chip that ran an operation, [(op name, line, start, end)]: the
    events of each ``/device:TPU:<i>`` plane's ``XLA Ops`` lines, keeping
    the line each ran on. A trace with no such event (the CPU backend) gives
    one list: the host events that carry an ``hlo_op`` stat."""
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [
                (trace.op_name(e.name), ln, e.start_ns, e.start_ns + e.duration_ns)
                for ln, line in enumerate(plane.lines)
                if line.name == "XLA Ops"
                for e in line.events
            ]
            if ops:
                devices.append(ops)
    if devices:
        return devices
    host = []
    for p, plane in enumerate(pd.planes):
        for ln, line in enumerate(plane.lines):
            for e in line.events:
                if any(k == "hlo_op" for k, _ in e.stats):
                    host.append((e.name, (p, ln), e.start_ns, e.start_ns + e.duration_ns))
    return [host]


def op_self_s(devices: list, window: tuple) -> dict:
    """Each op's self seconds inside ``window``, summed over chips: its
    duration less the ops it encloses on its line. Summed over ops, this is
    at most the chips' busy time, where :attr:`bench.trace.Summary.op_s`
    counts a loop and its body both."""
    lo, hi = window
    out: dict = defaultdict(float)
    for evs in devices:
        lines = defaultdict(list)
        for name, ln, s, e in evs:
            for iv in trace.clip([(s, e)], lo, hi):
                lines[ln].append((name, *iv))
        for ops in lines.values():
            for (name, _, _), own in zip(ops, nest([(s, e) for _, s, e in ops])):
                out[name] += own / 1e9
    return dict(out)


def gbps(spans: list, name: str):
    """Σ ``nbytes`` over Σ duration of the spans named ``name``, in GB/s."""
    chosen = [sp for sp in spans if sp.name == name]
    seconds = sum(sp.dur for sp in chosen) / 1e9
    nbytes = sum(int(sp.stats.get("nbytes", 0)) for sp in chosen)
    return nbytes / seconds / 1e9 if chosen and seconds > 0 else None


def hashed_per_sent(spans: list):
    """Bytes the content key hashed per byte a SEND brought in."""
    hashed = sum(int(sp.stats.get("nbytes", 0)) for sp in spans if sp.name == "al.store.key")
    sent = sum(int(sp.stats.get("nbytes", 0)) for sp in spans if sp.name == "al.wire.recv")
    return hashed / sent if sent else None


def wait_ms(spans: list):
    """Mean wait of a task in the task queue, submit to pick-up, in ms."""
    waits = [
        float(sp.stats["queued_us"])
        for sp in spans
        if sp.name.startswith("al.task.") and "queued_us" in sp.stats
    ]
    return sum(waits) / len(waits) / 1e3 if waits else None


def coverage(outer: tuple, spans: list, names=SEND_PATH) -> float:
    """Share of the (start, end) interval ``outer`` that the union of the
    spans named in ``names``, on any thread, covers."""
    s, e = outer
    inside = trace.clip([(sp.start, sp.end) for sp in spans if sp.name in names], s, e)
    covered = trace.union(inside)
    return sum(b - a for a, b in covered) / (e - s) if e > s else 0.0


@dataclass
class Reduced:
    window: tuple  # (start, end) ns of the harness's window
    verbs: list  # (name, start, end) of the harness's verb spans
    spans: list  # the window's al.* spans
    idle_s: dict  # idle_by_span
    op_self_s: dict


def reduce(path: str) -> Reduced:
    """The trace at ``path`` over its ``bench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    bench_spans = trace._host_spans(pd)
    (window,) = [(s, e) for name, s, e in bench_spans if name == trace.WINDOW]
    verbs = [sp for sp in bench_spans if sp[0] != trace.WINDOW]
    lo, hi = window
    spans = [sp for sp in host_spans(pd) if sp.start >= lo and sp.end <= hi]
    devices = device_ops(pd)
    ops = [[(name, s, e) for name, _, s, e in evs] for evs in devices]
    return Reduced(
        window=window,
        verbs=verbs,
        spans=spans,
        idle_s=idle_by_span(window, verbs, spans, ops),
        op_self_s=op_self_s(devices, window),
    )


def report(r: Reduced, top: int = 15) -> dict:
    """What :func:`reduce` found, as one JSON-ready object."""
    lo, hi = r.window
    by_name: dict = defaultdict(lambda: [0, 0.0, 0.0])
    for sp in r.spans:
        agg = by_name[sp.name]
        agg[0] += 1
        agg[1] += sp.dur / 1e9
        agg[2] += sp.self_ns / 1e9
    sends = [(s, e) for name, s, e in r.verbs if name == "bench.send" and s >= lo and e <= hi]
    return {
        "window_s": (hi - lo) / 1e9,
        "spans": {
            name: {"n": n, "total_s": t, "self_s": own}
            for name, (n, t, own) in sorted(by_name.items(), key=lambda kv: -kv[1][2])
        },
        "idle_gaps": sorted(r.idle_s.items(), key=lambda kv: -kv[1]),
        "device_ops_self": sorted(r.op_self_s.items(), key=lambda kv: -kv[1])[:top],
        "send_coverage": [coverage(iv, r.spans) for iv in sends],
        "quantities": {
            "wire.recv_GBps": gbps(r.spans, "al.wire.recv"),
            "store.key_GBps": gbps(r.spans, "al.store.key"),
            "store.hashed_per_sent": hashed_per_sent(r.spans),
            "device.put_GBps": gbps(r.spans, "al.device.put"),
            "wire.fetch_GBps": gbps(r.spans, "al.wire.fetch"),
            "taskqueue.wait_ms": wait_ms(r.spans),
        },
    }


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    args = ap.parse_args(argv)
    print(json.dumps(report(reduce(args.trace)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
