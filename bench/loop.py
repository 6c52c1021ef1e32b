"""The one job generator: a closed loop of one client over the served path.

A traffic file (``bench/traffic/<name>.json``) sets its parameters:

- ``operands``: ``"resident"`` sends the configuration's operands once, in
  set-up, and every job reuses them; ``"fresh"`` sends stamped copies in
  every job and frees them at its end;
- ``collect``: ``"outputs"`` brings every output of the routine to the host;
  ``"normest"`` keeps them resident, runs the library's ``normest`` on the
  first, and brings back only that scalar;
- ``warm_jobs``: jobs run in set-up, untimed, so that the window compiles
  nothing;
- ``check_jobs``: how many of the window's jobs the check compares, drawn
  from the seed.

Every verb goes through the session exactly as a user's would, under the
``eager`` policy, and is recorded as a span: ``jax.profiler.TraceAnnotation``
named ``bench.<verb>`` for the device trace, and a host-clock interval with
the bytes it moved for the per-layer metrics.
"""

from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager
from dataclasses import dataclass

import jax

@dataclass(frozen=True)
class Traffic:
    operands: str
    collect: str
    warm_jobs: int
    check_jobs: int

    @classmethod
    def load(cls, path: str) -> "Traffic":
        with open(path) as f:
            raw = json.load(f)
        t = cls(**{key: raw[key] for key in ("operands", "collect", "warm_jobs", "check_jobs")})
        if t.operands not in ("resident", "fresh") or t.collect not in ("outputs", "normest"):
            raise ValueError(f"{os.path.basename(path)}: unknown operands or collect: {raw}")
        if t.check_jobs < 1 or t.warm_jobs < 1:
            raise ValueError(f"{os.path.basename(path)}: needs a warm job and a checked job")
        return t


@dataclass
class Span:
    verb: str
    t0: float
    t1: float
    nbytes: int


class Client:
    """Drives one session through the traffic's jobs and records the spans."""

    def __init__(self, session, problem, traffic: Traffic):
        self.session, self.problem, self.traffic = session, problem, traffic
        self.spans: list[Span] = []
        self.resident: dict = {}

    @contextmanager
    def _span(self, verb: str, nbytes: int = 0):
        span = Span(verb, time.perf_counter(), 0.0, nbytes)
        with jax.profiler.TraceAnnotation(f"bench.{verb}"):
            yield span
        span.t1 = time.perf_counter()
        self.spans.append(span)

    def _send(self, name: str, array):
        with self._span("send", array.nbytes):
            return self.session.send(array, name=name)

    def _run(self, library: str, routine: str, args, n_outputs: int = 1, **params):
        with self._span("run"):
            out = self.session.run(
                library, routine, *args, n_outputs=n_outputs, cse=False, **params
            )
        return out if isinstance(out, tuple) else (out,)

    def _collect(self, handle):
        with self._span("collect") as span:
            value = handle.data()
            span.nbytes = getattr(value, "nbytes", 0)
        return value

    def _free(self, handle) -> None:
        with self._span("free"):
            handle.free()

    def setup(self) -> None:
        """Send the resident operands; the client then drops what the check
        does not need."""
        if self.traffic.operands == "resident":
            for name, array in self.problem.operands.items():
                self.resident[name] = self._send(name, array)
            self.problem.drop_operands()

    def job(self, j: int) -> dict:
        """One job; returns what it collected, by name."""
        r = self.problem.routine
        if self.traffic.operands == "fresh":
            sent = {name: self._send(name, x) for name, x in self.problem.fresh(j).items()}
        else:
            sent = self.resident
        outs = self._run(r.library, r.name, [sent[a] for a in r.args], len(r.outputs), **r.params)
        if self.traffic.collect == "outputs":
            collected = {name: self._collect(h) for name, h in zip(r.outputs, outs)}
        else:
            (norm,) = self._run(r.library, "normest", [outs[0]])
            collected = {"normest": self._collect(norm)}
        for h in outs:
            self._free(h)
        if self.traffic.operands == "fresh":
            for h in sent.values():
                self._free(h)
        return collected

    def close(self) -> None:
        for h in self.resident.values():
            h.free()
        self.resident = {}
