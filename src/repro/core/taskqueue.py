"""TaskQueue — per-session FIFO workers behind the asynchronous ACI.

DESIGN.md §3: the engine's concurrency unit is the *session*. Each session
owns one TaskQueue: a FIFO of send/run/collect tasks drained by a single
daemon worker thread. One worker per session keeps every session's operations
strictly ordered (the paper's per-application command stream, §2.4) while
letting *different* sessions — which own disjoint mesh slices — genuinely
overlap: their workers dispatch to XLA independently, and JAX's async
dispatch means a dispatched routine keeps computing while the same worker
already stages the next transfer.

The queue is intentionally tiny: tasks are plain callables, results flow
through :class:`~repro.core.futures.AlFuture`, and a barrier is just a no-op
task whose future the caller waits on. ServeEngine reuses the same class for
request batches, so the primitive is engine-wide, not Alchemist-specific.
"""

from __future__ import annotations

import queue
import re
import threading
import time
from typing import Any, Callable, Optional

from jax.profiler import TraceAnnotation

from repro.core.errors import QueueClosedError, TaskError
from repro.core.futures import AlFuture

_SHUTDOWN = object()
_KIND = re.compile(r"\w+")


def span_name(label: str) -> str:
    """The profiler span of a task: ``al.task.`` and the label's leading word
    (``send:x`` → ``al.task.send``, ``batch[3]`` → ``al.task.batch``), or
    ``al.task.task`` where the label starts with none (``<lambda>``)."""
    kind = _KIND.match(label)
    return f"al.task.{kind.group() if kind else 'task'}"


class TaskQueue:
    """A FIFO of callables drained by one lazily-started daemon worker."""

    def __init__(self, name: str = "taskqueue"):
        self.name = name
        self._q: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._closed = False
        self.tasks_submitted = 0
        self.tasks_completed = 0
        self.tasks_failed = 0
        # Deepest backlog ever observed at submit time: how far ahead of the
        # worker the client ran. The memory governor's reservations track the
        # bytes side of the same pipelining (DESIGN.md §7).
        self.max_backlog = 0
        # Cumulative ns the worker spent executing tasks, plus the start of
        # the currently-running task (None while idle). The data plane's
        # overlap accounting (DESIGN.md §10) diffs busy_ns() across an async
        # spill copy-out to measure how much compute the copy hid behind.
        self._busy_total_ns = 0
        self._busy_since: Optional[int] = None
        # Cumulative ns tasks waited in the queue, from submit to the
        # worker's pick-up: the queue's "time work waited".
        self.wait_ns = 0

    # -- submission ----------------------------------------------------------
    def submit(self, fn: Callable[[], Any], *, label: str = "") -> AlFuture:
        """Enqueue ``fn`` for the worker; returns the future of its result."""
        future = AlFuture(label=label or getattr(fn, "__name__", "task"))
        with self._lock:
            if self._closed:
                raise QueueClosedError(f"TaskQueue {self.name!r} is closed")
            self.tasks_submitted += 1
            self._q.put((fn, future, time.perf_counter_ns()))
            self.max_backlog = max(self.max_backlog, self._q.qsize())
            self._ensure_worker()
        return future

    def barrier(self, timeout: Optional[float] = None) -> None:
        """Block until every task submitted before this call has finished."""
        with self._lock:
            thread = self._thread
            if thread is None:
                return  # no worker was ever started: nothing in flight
            if self._closed:
                # close(wait=False) leaves the worker draining in the
                # background; "all tasks finished" then means "worker exited"
                # (it stops at the shutdown sentinel, which is queued last).
                future = None
            else:
                future = AlFuture(label=f"barrier:{self.name}")
                # Counted as submitted: the worker counts it completed, and
                # the submitted == completed + failed + pending invariant is
                # what the soak tests lean on.
                self.tasks_submitted += 1
                self._q.put((lambda: None, future, time.perf_counter_ns()))
        if future is not None:
            future.result(timeout)
            return
        thread.join(timeout)
        if thread.is_alive():
            raise TaskError(
                f"TaskQueue {self.name!r} barrier: worker still draining after {timeout}s"
            )

    # -- worker --------------------------------------------------------------
    def _ensure_worker(self) -> None:
        # caller holds self._lock
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._drain, name=f"{self.name}-worker", daemon=True
            )
            self._thread.start()

    def _drain(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is _SHUTDOWN:
                    return
                fn, future, submitted = item
                self._busy_since = time.perf_counter_ns()
                waited = self._busy_since - submitted
                self.wait_ns += waited
                try:
                    with TraceAnnotation(span_name(future.label), queued_us=waited / 1e3):
                        result = fn()
                    future._set_result(result)
                    self.tasks_completed += 1
                except BaseException as exc:  # noqa: BLE001 — propagate via future
                    self.tasks_failed += 1
                    future._set_exception(exc)
                finally:
                    start = self._busy_since
                    self._busy_since = None
                    if start is not None:
                        self._busy_total_ns += time.perf_counter_ns() - start
            finally:
                self._q.task_done()

    # -- lifecycle -----------------------------------------------------------
    @property
    def closed(self) -> bool:
        return self._closed

    def pending(self) -> int:
        """Approximate number of tasks not yet picked up by the worker."""
        return self._q.qsize()

    def busy_ns(self) -> int:
        """Cumulative ns the worker has spent executing tasks, including the
        one currently running. Monotone; racy reads are fine (the single
        writer is the worker thread, and the overlap accounting that diffs
        this only needs a lower bound on busy time)."""
        total, since = self._busy_total_ns, self._busy_since
        if since is not None:
            total += max(time.perf_counter_ns() - since, 0)
        return total

    def close(self, wait: bool = True, timeout: Optional[float] = None) -> None:
        """Stop accepting tasks; optionally drain what's already queued.

        Idempotent. With ``wait=False`` the already-queued tasks still run
        (the worker drains them in the background) but we don't block on them.
        """
        with self._lock:
            if self._closed:
                return
            self._closed = True
            thread = self._thread
            if thread is not None:
                self._q.put(_SHUTDOWN)
        if wait and thread is not None:
            thread.join(timeout)
            if thread.is_alive():
                raise TaskError(
                    f"TaskQueue {self.name!r} failed to drain within {timeout}s"
                )

    def stats(self) -> dict:
        return {
            "submitted": self.tasks_submitted,
            "completed": self.tasks_completed,
            "failed": self.tasks_failed,
            "max_backlog": self.max_backlog,
            "wait_ns": self.wait_ns,
        }

    def __repr__(self) -> str:
        return (
            f"TaskQueue({self.name!r}, submitted={self.tasks_submitted}, "
            f"completed={self.tasks_completed}, failed={self.tasks_failed}, "
            f"closed={self._closed})"
        )


class TransferExecutor:
    """Dedicated copy worker behind the asynchronous data plane (DESIGN.md §10).

    One daemon thread drains D2H copy-out jobs so a session's queue worker can
    dispatch the next task while the previous spill victim's bytes stream to
    host. The ring is a bounded double buffer: at most ``ring`` jobs may be
    queued or copying at once, so device memory overshoot from not-yet-copied
    victims is capped at two matrices. :meth:`try_submit` is strictly
    non-blocking — the memory governor calls it under its lock, and the worker
    needs that same lock to complete a job, so a blocking submit would
    deadlock; a full ring returns None and the caller copies synchronously.
    """

    def __init__(self, name: str = "transfer", ring: int = 2):
        self.name = name
        self.ring = ring
        self._q: "queue.Queue" = queue.Queue()
        self._slots = threading.Semaphore(ring)
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._in_flight = 0
        self.submitted = 0
        self.rejected = 0  # ring full: the caller fell back to a sync copy
        self.max_depth = 0

    def try_submit(self, fn: Callable[[], None]) -> bool:
        """Enqueue ``fn`` if a ring slot is free; False means ring full."""
        if not self._slots.acquire(blocking=False):
            self.rejected += 1
            return False
        with self._lock:
            if self._closed:
                self._slots.release()
                self.rejected += 1
                return False
            self.submitted += 1
            self._in_flight += 1
            self.max_depth = max(self.max_depth, self._in_flight)
            self._q.put(fn)
            self._ensure_worker()
        return True

    def depth(self) -> int:
        """Jobs queued or copying right now (0..ring)."""
        return self._in_flight

    def _ensure_worker(self) -> None:
        # caller holds self._lock
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._drain, name=f"{self.name}-worker", daemon=True
            )
            self._thread.start()

    def _drain(self) -> None:
        while True:
            fn = self._q.get()
            try:
                if fn is _SHUTDOWN:
                    return
                try:
                    fn()
                except BaseException:  # noqa: BLE001 — a copy job must never
                    pass  # kill the ring; the job owner observes via its event
                finally:
                    with self._lock:
                        self._in_flight -= 1
                    self._slots.release()
            finally:
                self._q.task_done()

    def close(self, wait: bool = True, timeout: Optional[float] = None) -> None:
        """Stop accepting jobs; optionally wait for queued copies to finish."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            thread = self._thread
            if thread is not None:
                self._q.put(_SHUTDOWN)
        if wait and thread is not None:
            thread.join(timeout)

    def stats(self) -> dict:
        return {
            "submitted": self.submitted,
            "rejected": self.rejected,
            "max_depth": self.max_depth,
            "ring": self.ring,
        }

    def __repr__(self) -> str:
        return (
            f"TransferExecutor({self.name!r}, ring={self.ring}, "
            f"submitted={self.submitted}, rejected={self.rejected})"
        )
