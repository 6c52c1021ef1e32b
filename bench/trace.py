"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

- Device busy time: the union of the intervals in which an operation ran on
  a device, inside the window (the harness's ``bench.window`` span), averaged
  over the chips the cell uses.
- Collective time: the part of each chip's busy time in which a collective
  was in flight (an ``all-reduce``, ``all-gather``, ``reduce-scatter``,
  ``collective-permute`` or ``all-to-all``; an async ``-start.N``/``-done.N``
  pair from the start of the one to the end of the other), inside the
  window, averaged over the chips. A pair's stretches with no op running on
  the chip are idle time, not collective time, so the share never passes
  the busy time.
- Device time by operation: summed durations, per op name.
- Idle time by host activity: each idle interval of the device, split by the
  harness's verb spans (``bench.send``, ``bench.run``, ``bench.collect``,
  ``bench.free``) it overlaps on the same clock; what no verb covers is the
  harness's own time between verbs (``bench.between``).
- Host time by verb: each verb span's length less the part of it in which
  the device was busy, so that a verb that waits on device work counts only
  its own time.

On a TPU the operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane that holds some, ``i`` below the cell's chip count
where that is given: a cell's group is the host's first chips, and a trace
in which another number of them ran ops is refused. A trace with
no device plane (the CPU backend, in the rehearsals) takes the host events
that carry an ``hlo_op`` stat.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"
VERB_PREFIX = "bench."
BETWEEN = "bench.between"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")


@dataclass
class Summary:
    window_s: float
    busy_s: float  # per chip, averaged over the chips used
    chips: int = 1  # the chips whose ops were read
    collective_s: float = 0.0  # busy time with a collective in flight, per chip, averaged
    op_s: dict = field(default_factory=dict)  # op name -> seconds, summed over chips
    idle_s: dict = field(default_factory=dict)  # host activity -> seconds, mean over chips
    verb_n: dict = field(default_factory=dict)  # verb -> spans in the window
    verb_host_s: dict = field(default_factory=dict)  # verb -> seconds the device was not busy

    def top_ops(self, n: int = 10) -> list:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]

    def top_idle(self, n: int = 10) -> list:
        return sorted(self.idle_s.items(), key=lambda kv: -kv[1])[:n]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {len(paths)}")
    return paths[0]


def union(intervals: list) -> list:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of disjoint, ordered ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(spans: list, busy: list) -> list:
    """For each of the disjoint, ordered (start, end) ``spans``, the length of
    it that disjoint, ordered ``busy`` covers."""
    out, first = [], 0
    for s, e in spans:
        while first < len(busy) and busy[first][1] <= s:
            first += 1
        covered, i = 0, first
        while i < len(busy) and busy[i][0] < e:
            covered += min(e, busy[i][1]) - max(s, busy[i][0])
            i += 1
        out.append(covered)
    return out


def collective_intervals(ops: list) -> list:
    """The (start, end) intervals of one chip's ``(name, start, end)`` ops in
    which a collective was in flight: a synchronous one's own interval, and
    an async pair's from its ``<kind>-start.N`` to the next ``<kind>-done.N``
    of the same ``N`` (none for a bare ``-start``/``-done``)."""
    out, started = [], defaultdict(list)
    for name, s, e in sorted(ops, key=lambda op: op[1]):
        kind = next((c for c in COLLECTIVES if name.startswith(c)), None)
        if kind is None:
            continue
        rest = name[len(kind) :]
        half = next((h for h in ("-start", "-done") if rest.startswith(h)), None)
        if half is None:
            out.append((s, e))
            continue
        pending = started[kind, rest[len(half) :]]
        if half == "-start":
            pending.append(s)
        elif pending:
            out.append((pending.pop(0), e))
    return out


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def op_name(text: str) -> str:
    """An op's name without its HLO text: ``%while.24 = (...) while(...)`` is
    ``while.24``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _device_events(pd, chips: int | None = None) -> dict:
    """{device index: [(name, start_ns, end_ns), ...]} of each TPU that ran an
    op, ``chips`` of them at most where given (a cell's group is the host's
    first chips)."""
    devices: dict = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        idx = int(plane.name.rsplit(":", 1)[1])
        if chips is not None and idx >= chips:
            continue
        evs = [
            (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
            for line in plane.lines
            if line.name == "XLA Ops"
            for e in line.events
        ]
        if evs:
            devices[idx] = evs
    if devices:
        return devices
    host = []  # no device plane: the CPU backend's ops, on host threads
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if _stat(e, "hlo_op") is not None:
                    host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return {0: host}


def _host_spans(pd) -> list:
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(VERB_PREFIX):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def reduce(path: str, chips: int | None = None) -> Summary:
    """Summarise the trace at ``path`` over its ``bench.window`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = _host_spans(pd)
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span in the trace, found {len(windows)}")
    verbs = [span for span in spans if span[0] != WINDOW]
    devices = _device_events(pd, chips)
    on_tpu = any(plane.name.startswith("/device:TPU:") for plane in pd.planes)
    if on_tpu and chips is not None and len(devices) != chips:
        raise ValueError(f"the trace holds the ops of {len(devices)} chips, the cell has {chips}")
    return summarize(windows[0], verbs, list(devices.values()))


def summarize(window: tuple, verbs: list, devices: list) -> Summary:
    """``window`` (start, end); ``verbs`` [(name, start, end)] on one host
    thread; ``devices`` one list of (op name, start, end) per chip; all in ns."""
    lo, hi = window
    verbs = sorted((s, e, name) for name, s, e in verbs)
    verbs = [(s, e, name) for s, e, name in verbs if s >= lo and e <= hi]
    busy_total, collective_total = 0.0, 0.0
    op_s, idle_s = defaultdict(float), defaultdict(float)
    verb_n, verb_host_s = defaultdict(int), defaultdict(float)
    for _, _, name in verbs:
        verb_n[name] += 1
    for evs in devices:
        inside = [(name, *iv) for name, s, e in evs for iv in clip([(s, e)], lo, hi)]
        for name, s, e in inside:
            op_s[name] += (e - s) / 1e9
        busy = union([(s, e) for _, s, e in inside])
        busy_total += sum(e - s for s, e in busy) / 1e9
        collective = union(clip(collective_intervals(evs), lo, hi))
        collective_total += sum(overlap(collective, busy)) / 1e9
        covered = overlap([(s, e) for s, e, _ in verbs], busy)
        for (s, e, name), c in zip(verbs, covered):
            verb_host_s[name] += (e - s - c) / 1e9 / len(devices)
        first = 0  # verbs run on one thread, so they are disjoint and ordered
        for g0, g1 in gaps(busy, lo, hi):
            while first < len(verbs) and verbs[first][1] <= g0:
                first += 1
            covered, i = 0.0, first
            while i < len(verbs) and verbs[i][0] < g1:
                s, e, name = verbs[i]
                over = min(e, g1) - max(s, g0)
                idle_s[name] += over / 1e9 / len(devices)
                covered += over
                i += 1
            idle_s[BETWEEN] += ((g1 - g0) - covered) / 1e9 / len(devices)
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / len(devices),
        chips=len(devices),
        collective_s=collective_total / len(devices),
        op_s=dict(op_s),
        idle_s=dict(idle_s),
        verb_n=dict(verb_n),
        verb_host_s=dict(verb_host_s),
    )
