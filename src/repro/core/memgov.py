"""MemoryGovernor — engine-wide budgeted spill/refill of resident matrices.

DESIGN.md §7/§8. Alchemist's value proposition is keeping matrices resident
on the engine so drivers avoid repeated transfers (arXiv:1806.01270), but
residency pins HBM until an explicit free — exactly the memory pressure the
deployment follow-up flags as the limiting factor for long offload pipelines
(arXiv:1910.01354). The governor bounds it, and it bounds it **engine-wide**:
one governor per :class:`~repro.core.engine.AlchemistEngine`, shared by every
connected session, so multi-tenant pressure is charged against a single
budget instead of N independent ones that sum to N× the hardware.

- every materialized :class:`~repro.core.handles.AlMatrix` of every session
  is **charged** its physical byte footprint (logical extent plus
  divisibility padding) against the shared budget;
- before a send/attach stages bytes or a routine materializes outputs, the
  task **admits** the incoming footprint: least-recently-used resident
  matrices — preferring ones a planner has hinted as past their DAG last
  use — are **spilled** until the new bytes fit. Victims are chosen *across
  sessions*, but a matrix pinned by a live run in any session is never
  spilled;
- a spilled handle stays *live*: its next consumption (``data()``) triggers
  a transparent **refill** through its own session's cached relayout plan.
  Store-backed placements (DESIGN.md §8) spill for free — their logical
  payload already sits host-side on the entry, so the spill just drops the
  device bytes and the refill re-places from the payload;
- ``reserve``/``unreserve`` track bytes promised by not-yet-executed queued
  tasks across all sessions, so ``pressure()`` forecasts engine demand.

The **effective budget** is the minimum of the engine's base budget
(``AlchemistEngine(hbm_budget=...)`` or :meth:`set_budget`) and every live
session's requested budget (``AlchemistContext(hbm_budget=...)`` →
:meth:`request_budget`): the most conservative live constraint wins, which
keeps single-session semantics identical to the old per-session governor
while giving concurrent sessions one shared ceiling.

The governor is deliberately an *accounting* model — it charges the bytes
the engine placed, rather than querying allocator internals — which keeps
the policy identical on emulated-CPU meshes and real HBM. Per-handle stats
(spill/refill/high-water) land on the owning session's ``SessionStats``;
:attr:`high_water` tracks the engine-wide maximum for multi-tenant gates.

With no budget anywhere (the default) nothing spills and the governor is
pure bookkeeping.

**The asynchronous data plane (DESIGN.md §10).** Spill copy-outs are enqueued
onto a dedicated :class:`~repro.core.taskqueue.TransferExecutor` (a bounded
double-buffer ring) so the owning session's queue worker overlaps the next
task's compute with the previous victim's D2H. Only the *state transition*
runs under the governor lock; the bytes stream on the transfer thread, with
an ``in_flight_spill_bytes`` ledger tracking victims whose device reference
is still held pending copy. A refill of a still-in-flight victim *joins* the
pending copy — it cancels the job and restores the retained device array,
zero copies — and a collect of one waits on the job's event. Host staging
buffers come from a small reuse pool and are donated back after refill,
eliminating one host copy per spill/refill cycle; a buffer served to a client
(``host_payload``) is marked read-only and never recycled, and a buffer the
refill's zero-copy ``device_put`` aliased stays owned by the device array
(pooling it would let a later gather corrupt the resident matrix).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from typing import TYPE_CHECKING, Dict, Iterable, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import handles as handles_mod
from repro.core.errors import HandleError
from repro.core.handles import AlMatrix
from repro.core.payload import aliases_host, place
from repro.core.relayout import pad_amounts
from repro.core.taskqueue import TransferExecutor

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids an import cycle
    from repro.core.session import Session

_CLOCK = itertools.count(1)


@dataclasses.dataclass
class _SpillJob:
    """One victim's pending copy-out on the transfer ring.

    ``array`` holds the device reference until the copy lands (or a refill
    joins / a free cancels); whoever nulls it under the governor lock also
    decrements the in-flight ledger, exactly once. ``event`` is set when the
    job reaches a terminal state (done, cancelled, failed) — collect-side
    waiters key off it.
    """

    handle: AlMatrix
    array: Optional[jax.Array]
    nbytes: int
    state: str = "queued"  # queued -> copying -> done | cancelled | failed
    event: threading.Event = dataclasses.field(default_factory=threading.Event)


class _StagingPool:
    """Small pool of reusable host staging buffers for spill copy-outs.

    ``release`` refuses read-only buffers: ``host_payload`` marks a buffer
    read-only the moment it escapes to a client (collects may serve it
    zero-copy), so an escaped buffer can never be handed to a later spill's
    ``gather`` and corrupted under the client.
    """

    def __init__(self, max_buffers: int = 4):
        self._free: List[np.ndarray] = []
        self._lock = threading.Lock()
        self.max_buffers = max_buffers
        self.reuses = 0

    def acquire(self, shape, dtype) -> np.ndarray:
        with self._lock:
            for i, buf in enumerate(self._free):
                if buf.shape == tuple(shape) and buf.dtype == dtype:
                    self.reuses += 1
                    return self._free.pop(i)
        return np.empty(tuple(shape), dtype)

    def release(self, buf) -> None:
        if not isinstance(buf, np.ndarray) or not buf.flags.writeable:
            return  # escaped to a client, or a foreign (store-owned) payload
        with self._lock:
            if len(self._free) < self.max_buffers and all(b is not buf for b in self._free):
                self._free.append(buf)

    def clear(self) -> None:
        with self._lock:
            self._free.clear()


def _validate_budget(budget: Optional[int]) -> Optional[int]:
    if budget is not None and budget <= 0:
        raise ValueError(f"hbm budget must be positive or None, got {budget}")
    return budget


class MemoryGovernor:
    """Engine-wide HBM budget: charge, spill, refill (DESIGN.md §7/§8)."""

    def __init__(
        self,
        budget: Optional[int] = None,
        name: str = "memgov",
        async_spill: bool = True,
    ):
        self._base_budget = _validate_budget(budget)
        self.name = name
        self._sessions: Dict[int, "Session"] = {}
        self._session_budgets: Dict[int, int] = {}
        self._lock = threading.RLock()
        # handle id -> handle, for every charged (materialized or spilled)
        # matrix of any session; _charged holds the bytes each one was
        # charged at.
        self._handles: Dict[int, AlMatrix] = {}
        self._charged: Dict[int, int] = {}
        # the pinned host store: physical (padded) payloads of spilled
        # handles that have no store-entry fallback to refill from.
        self._host_store: Dict[int, np.ndarray] = {}
        self._touch: Dict[int, int] = {}
        self._pin_counts: Dict[int, int] = {}
        self._idle: Set[int] = set()  # planner last-use hints: spill these first
        self._used = 0
        self._reserved = 0
        #: engine-wide maximum of simultaneously charged bytes — the number
        #: the multi-tenant acceptance gate bounds against the shared budget.
        self.high_water = 0
        # Asynchronous data plane (DESIGN.md §10): pending copy-outs by
        # handle id, the device bytes they still retain, the transfer ring
        # (built lazily on first async spill), and the host staging pool.
        self.async_spill = bool(async_spill)
        self._in_flight: Dict[int, _SpillJob] = {}
        self._in_flight_bytes = 0
        self._transfer: Optional[TransferExecutor] = None
        self._staging = _StagingPool()
        # Pressure watermarks (DESIGN.md §12): fractions of the effective
        # budget gating new *private* placements in the scheduler, with
        # hysteresis — block above high, resume only below low.
        self._watermarks: Optional[Tuple[float, float]] = None
        self._gated = False
        # Shared-group views (DESIGN.md §12): view handle id -> source handle
        # id. A view is never charged (its bytes belong to the source
        # placement); instead the source is pinned so it cannot be spilled
        # out from under a reader in another session.
        self._view_sources: Dict[int, int] = {}

    # -- session membership ---------------------------------------------------
    def attach_session(
        self, session: "Session", hbm_budget: Optional[int] = None
    ) -> None:
        """A session connected: route its handles' spill/refill through its
        mesh + relayout cache, and fold its requested budget into the shared
        ceiling. Validates the budget *before* registering anything — a
        rejected budget must not leave a ghost session in the engine-wide
        ledger."""
        _validate_budget(hbm_budget)
        with self._lock:
            self._sessions[session.id] = session
            if hbm_budget is not None:
                self._session_budgets[session.id] = hbm_budget

    def detach_session(self, session_id: int) -> None:
        """Session closed: its handles were freed/migrated by the session
        layer; drop its budget request from the shared ceiling."""
        with self._lock:
            self._sessions.pop(session_id, None)
            self._session_budgets.pop(session_id, None)

    def bind(self, session: "Session") -> None:
        """Backwards-compatible alias of :meth:`attach_session`."""
        self.attach_session(session)

    @property
    def budget(self) -> Optional[int]:
        """The effective shared budget: min over the engine's base budget and
        every live session's request; None when nothing constrains."""
        with self._lock:
            constraints = [b for b in self._session_budgets.values()]
            if self._base_budget is not None:
                constraints.append(self._base_budget)
            return min(constraints) if constraints else None

    @property
    def base_budget(self) -> Optional[int]:
        """The engine's own budget, before session requests tighten it — what
        a scoped override (``offloaded(hbm_budget=...)``) must save/restore;
        restoring the *effective* value would bake one session's request into
        the engine for good."""
        with self._lock:
            return self._base_budget

    def set_budget(self, budget: Optional[int]) -> None:
        """Change the engine's base budget (e.g. a scoped override via
        ``offload.offloaded(ac, hbm_budget=...)``), with the same validation
        as construction. Serialized against admissions: an admit() in flight
        on a queue worker finishes under the budget it snapshotted."""
        _validate_budget(budget)
        with self._lock:
            self._base_budget = budget

    def request_budget(self, session_id: int, budget: Optional[int]) -> None:
        """Fold a per-session budget request into the shared ceiling."""
        with self._lock:
            if budget is None:
                self._session_budgets.pop(session_id, None)
            else:
                self._session_budgets[session_id] = _validate_budget(budget)

    def requested_budget(self, session_id: int) -> Optional[int]:
        """The session's current budget request (None if it has none) — what
        a scoped per-session override must save and restore."""
        with self._lock:
            return self._session_budgets.get(session_id)

    @property
    def lock(self) -> threading.RLock:
        """The governor's reentrant lock. Handle reads hold it across the
        check-refill-slice sequence (`AlMatrix.data()`), so a client-thread
        read can never observe a half-spilled handle from a queue worker."""
        return self._lock

    @property
    def staging(self) -> _StagingPool:
        """The host staging-buffer pool — shared with the wire's shard-direct
        receive path (DESIGN.md §13), so slabs recycle across receives and
        spill copy-outs alike."""
        return self._staging

    def transfer_ring(self) -> TransferExecutor:
        """The bounded double-buffer transfer executor (DESIGN.md §10) —
        also the ring the shard-direct receiver rides for eager per-shard
        ``device_put``s overlapping socket reads."""
        return self._executor()

    def unbudgeted(self) -> bool:
        """True when no HBM budget constrains admission (engine-wide or
        per-session). The shard-direct receiver only issues *eager* device
        puts in this regime: under a budget, bytes may not land on device
        before ``admit()`` has made room, so puts defer to the send task."""
        with self._lock:
            return self._base_budget is None and not self._session_budgets

    # -- accounting ----------------------------------------------------------
    @property
    def used(self) -> int:
        """Bytes currently charged against the budget (device-resident)."""
        return self._used

    @property
    def reserved(self) -> int:
        """Bytes promised by queued-but-not-yet-executed tasks."""
        return self._reserved

    def pressure(self) -> int:
        """Forecast demand: resident bytes plus outstanding reservations."""
        with self._lock:
            return self._used + self._reserved

    def reserve(self, nbytes: int) -> int:
        """Client-side, before enqueueing: promise ``nbytes`` of residency.
        Returns the reservation size (pass it back to :meth:`unreserve`)."""
        nbytes = max(int(nbytes), 0)
        with self._lock:
            self._reserved += nbytes
        return nbytes

    def unreserve(self, nbytes: int) -> None:
        """Task-side: the reservation was converted to a charge (or the task
        failed); drop it from the forecast."""
        with self._lock:
            self._reserved = max(self._reserved - max(int(nbytes), 0), 0)

    # -- pressure watermarks (DESIGN.md §12) ---------------------------------
    def set_watermarks(self, high: float, low: float) -> None:
        """Enable (or retune) the admission pressure gate.

        ``high``/``low`` are fractions of the *effective* budget. When
        ``pressure()`` rises above ``high * budget`` new private placements
        stop admitting; they resume only once pressure falls below
        ``low * budget`` (hysteresis, so admission does not flap at the
        boundary). Pass via ``AlchemistEngine(pressure_watermarks=(h, l))``.
        """
        if not (0.0 < low <= high):
            raise ValueError(
                f"watermarks must satisfy 0 < low <= high, got high={high}, low={low}"
            )
        with self._lock:
            self._watermarks = (float(high), float(low))
            self._gated = False

    def clear_watermarks(self) -> None:
        """Disable the pressure gate (the free-pool count gates alone)."""
        with self._lock:
            self._watermarks = None
            self._gated = False

    @property
    def watermarks(self) -> Optional[Tuple[float, float]]:
        with self._lock:
            return self._watermarks

    @property
    def has_watermarks(self) -> bool:
        return self._watermarks is not None

    def admission_gate(self) -> bool:
        """True while governor pressure should block new private placements.

        With no watermarks (or no effective budget) the gate is always open.
        The hysteresis state flips closed when pressure exceeds the high
        watermark and reopens only below the low one.
        """
        with self._lock:
            if self._watermarks is None:
                return False
            budget = self.budget
            if budget is None:
                return False
            high, low = self._watermarks
            pressure = self._used + self._reserved
            if self._gated:
                if pressure < low * budget:
                    self._gated = False
            elif pressure > high * budget:
                self._gated = True
            return self._gated

    # -- admission -----------------------------------------------------------
    def admit(self, nbytes: int, exclude: Iterable[int] = ()) -> int:
        """Make room for ``nbytes`` of incoming residency — spilling unpinned
        victims (planner-hinted idle first, then least-recently-used, chosen
        across every session) until ``used + nbytes`` fits the shared budget —
        and **claim** the bytes: ``used`` grows by ``nbytes`` immediately, so
        a concurrent admission from another session cannot fill the approved
        room before the caller materializes into it (the engine-wide budget
        must hold across interleaved sessions, not just within one FIFO).
        Pair every admit with :meth:`settle` once the real charge landed (or
        the task failed). Returns the number of spills.

        Admission is *best effort*: if everything else is pinned or the
        incoming matrix alone exceeds the budget, the bytes are admitted
        anyway — the governor bounds memory, it never deadlocks the pipeline.
        """
        nbytes = max(int(nbytes), 0)
        spills = 0
        excluded = set(exclude)
        deferred: List[_SpillJob] = []
        # The pick-spill window runs under the lock: a concurrent refill on
        # another thread (itself an admission) must not spill our chosen
        # victim between the pick and the spill. The budget is snapshotted
        # under the same lock — a scoped override expiring mid-admission
        # (offloaded() exit flips it back) must not yank the loop's
        # comparison out from under it. Victim copy-outs land on the transfer
        # ring; when the ring is full they are deferred and copied
        # synchronously *after* the lock is released below (the satellite fix
        # for the old device_get-under-lock stall), so concurrent sessions'
        # reads never queue behind a bulk copy.
        with self._lock:
            budget = self.budget
            if budget is not None:
                while self._used + nbytes > budget:
                    victim = self._pick_victim(excluded)
                    if victim is None:
                        break
                    self.spill(victim, _deferred=deferred)
                    spills += 1
            self._used += nbytes
            self.high_water = max(self.high_water, self._used)
        for job in deferred:
            self._copy_out(job, on_ring=False)
        return spills

    def settle(self, nbytes: int) -> None:
        """Release an :meth:`admit` claim. Callers converting the claim into
        real charges do both under one lock hold —

            with memgov.lock:
                memgov.settle(admitted)
                memgov.charge(h)          # or new_handle(...), which charges

        — so no other session's admission can slip into the gap between the
        claim ending and the charge landing."""
        nbytes = max(int(nbytes), 0)
        with self._lock:
            self._used -= nbytes

    def _pick_victim(self, excluded: Set[int]) -> Optional[AlMatrix]:
        with self._lock:
            candidates: List[AlMatrix] = [
                h
                for hid, h in self._handles.items()
                if hid not in excluded
                and not self._pin_counts.get(hid)
                and h.state == handles_mod.MATERIALIZED
                and h._data is not None
            ]
            if not candidates:
                return None
            # Planner-hinted idle matrices (past their DAG last use) first,
            # then least-recently-touched — regardless of owning session.
            return min(
                candidates,
                key=lambda h: (h.id not in self._idle, self._touch.get(h.id, 0)),
            )

    # -- charge / discard ----------------------------------------------------
    def charge(self, h: AlMatrix) -> None:
        """Register a newly materialized matrix and charge its footprint."""
        h._governor = self
        nbytes = h.physical_nbytes()
        with self._lock:
            prev = self._charged.get(h.id, 0)
            self._handles[h.id] = h
            self._charged[h.id] = nbytes
            self._used += nbytes - prev
            self._touch[h.id] = next(_CLOCK)
            self._idle.discard(h.id)
            self._record_high_water(h)

    def discard(self, h: AlMatrix) -> None:
        """The handle was freed: drop its charge, any host-store bytes, and
        cancel a copy-out still in flight (its device reference just drops)."""
        with self._lock:
            self._handles.pop(h.id, None)
            self._used -= self._charged.pop(h.id, 0)
            popped = self._host_store.pop(h.id, None)
            if popped is not None:
                self._staging.release(popped)
            job = self._in_flight.pop(h.id, None)
            if job is not None:
                if job.array is not None:
                    job.array = None
                    self._in_flight_bytes -= job.nbytes
                job.state = "cancelled"
                job.event.set()
            self._touch.pop(h.id, None)
            self._pin_counts.pop(h.id, None)
            self._idle.discard(h.id)
            # Shared-group view teardown: the reader is gone, release its
            # pin on the source placement (which may itself already be gone
            # — the get() default absorbs that race).
            src_id = self._view_sources.pop(h.id, None)
            if src_id is not None:
                left = self._pin_counts.get(src_id, 0) - 1
                if left > 0:
                    self._pin_counts[src_id] = left
                else:
                    self._pin_counts.pop(src_id, None)

    def touch(self, h: AlMatrix) -> None:
        """Record a consumption: resets LRU age and clears any idle hint."""
        with self._lock:
            if h.id in self._handles:
                self._touch[h.id] = next(_CLOCK)
                self._idle.discard(h.id)

    def hint_idle(self, h: AlMatrix) -> None:
        """Planner hint: the DAG holds no further uses of this matrix — make
        it a preferred spill victim (it may still be collected or reused; a
        hint is a priority, not a free)."""
        with self._lock:
            if h.id in self._handles:
                self._idle.add(h.id)

    @contextlib.contextmanager
    def pinned(self, hs: Iterable[AlMatrix]):
        """Keep ``hs`` unspillable while a task consumes them (a refilled
        input must not be re-spilled by the admission of the next one) —
        respected by admissions from *every* session."""
        ids = [h.id for h in hs if isinstance(h, AlMatrix)]
        with self._lock:
            for hid in ids:
                self._pin_counts[hid] = self._pin_counts.get(hid, 0) + 1
        try:
            yield
        finally:
            with self._lock:
                for hid in ids:
                    left = self._pin_counts.get(hid, 1) - 1
                    if left > 0:
                        self._pin_counts[hid] = left
                    else:
                        self._pin_counts.pop(hid, None)

    def register_view(self, view: AlMatrix, source: AlMatrix) -> None:
        """Register a shared-group read view over another session's handle.

        The view shares the source's device array, so it is **not** charged
        (charging would double-count the same bytes); instead the source is
        pinned for the view's lifetime so no admission in any session can
        spill the bytes out from under the reader. The pin drops in
        :meth:`discard` when the view handle is freed.
        """
        with self._lock:
            view._governor = self
            self._view_sources[view.id] = source.id
            self._pin_counts[source.id] = self._pin_counts.get(source.id, 0) + 1

    # -- spill / refill ------------------------------------------------------
    def spill(self, h: AlMatrix, *, _deferred: Optional[List[_SpillJob]] = None) -> None:
        """Move a resident matrix's bytes off the worker group.

        Store-backed placements (a live ``_host_fallback``) spill for free:
        the engine already holds their logical payload host-side, so only the
        device array is dropped. Everything else becomes a :class:`_SpillJob`
        copy-out into the pinned host store. Only the *state transition* runs
        under the governor lock — a concurrent ``data()`` on another thread
        (handles hold the same lock across its check-refill-slice sequence)
        sees the handle either fully resident or fully spilled, never
        ``_data is None`` mid-flight — while the bytes stream on the transfer
        ring (or synchronously outside the lock when the ring is full or
        ``async_spill`` is off). The job retains the device reference until
        the copy lands, so a prompt refill joins it instead of re-reading the
        device; ``in_flight_spill_bytes`` ledgers exactly those bytes.
        """
        job: Optional[_SpillJob] = None
        with self._lock:
            if h.state != handles_mod.MATERIALIZED or h._data is None:
                raise HandleError(f"cannot spill AlMatrix {h.id} in state {h.state!r}")
            nbytes = self._charged.get(h.id, h.physical_nbytes())
            if h._host_fallback is None:
                job = _SpillJob(handle=h, array=h._data, nbytes=nbytes)
                self._in_flight[h.id] = job
                self._in_flight_bytes += nbytes
            self._used -= nbytes
            self._charged[h.id] = 0
            h._data = None
            h._state = handles_mod.SPILLED
        stats = self._stats_for(h)
        if stats is not None:
            stats.record_spill(nbytes)
        if job is None:
            return
        if self.async_spill and self._executor().try_submit(
            lambda: self._copy_out(job, on_ring=True)
        ):
            if stats is not None:
                stats.record_transfer_depth(self._transfer.depth())
            return
        # Ring full (double-buffer bound) or async disabled: copy on the
        # caller — after the admit loop's lock release when reached via
        # admission (_deferred), immediately otherwise.
        if _deferred is not None:
            _deferred.append(job)
        else:
            self._copy_out(job, on_ring=False)

    def _executor(self) -> TransferExecutor:
        with self._lock:
            if self._transfer is None or self._transfer._closed:
                self._transfer = TransferExecutor(name=f"{self.name}-transfer")
            return self._transfer

    def _gather_host(self, arr: jax.Array) -> np.ndarray:
        """Device→host copy into a pooled staging buffer (per-shard, one host
        write each); falls back to a plain ``device_get`` for arrays whose
        shards aren't addressable."""
        buf = self._staging.acquire(tuple(arr.shape), np.dtype(arr.dtype))
        try:
            for shard in arr.addressable_shards:
                buf[shard.index] = np.asarray(shard.data)
            return buf
        except Exception:  # pragma: no cover - non-addressable topologies
            self._staging.release(buf)
            return np.asarray(jax.device_get(arr))

    def _copy_out(self, job: _SpillJob, *, on_ring: bool) -> None:
        """Stream one spill victim's bytes to the host store.

        Runs on the transfer thread (``on_ring=True``) or the spilling caller
        (sync fallback). Claims the job under the lock, copies outside it,
        then installs under the lock again — a refill that joined (cancelled)
        the job meanwhile wins, and the gathered buffer goes back to the
        staging pool. Overlap accounting (ring copies only): the slice of the
        copy's wall time during which the owning session's queue worker was
        busy is compute the copy hid behind.
        """
        with self._lock:
            if job.state != "queued" or job.array is None:
                job.event.set()  # joined or cancelled before the copy began
                return
            job.state = "copying"
            arr = job.array
            sess = self._sessions.get(job.handle.session_id)
        tasks = sess.tasks if sess is not None else None
        busy0 = tasks.busy_ns() if tasks is not None else 0
        t0 = time.perf_counter_ns()
        try:
            host = self._gather_host(arr)
        except BaseException:  # pragma: no cover - device_get failure
            # The device reference is still good: restore residency rather
            # than lose the only copy of the bytes.
            with self._lock:
                if job.array is not None and self._in_flight.get(job.handle.id) is job:
                    job.array = None
                    self._in_flight_bytes -= job.nbytes
                    self._in_flight.pop(job.handle.id, None)
                    h = job.handle
                    if h.state == handles_mod.SPILLED and h.id in self._handles:
                        h._data = arr
                        h._state = handles_mod.MATERIALIZED
                        self._charged[h.id] = job.nbytes
                        self._used += job.nbytes
                job.state = "failed"
            job.event.set()
            return
        wall_ns = time.perf_counter_ns() - t0
        busy1 = tasks.busy_ns() if tasks is not None else 0
        installed = False
        with self._lock:
            if job.array is not None and self._in_flight.get(job.handle.id) is job:
                job.array = None
                self._in_flight_bytes -= job.nbytes
                self._in_flight.pop(job.handle.id, None)
                job.state = "done"
                if job.handle.state == handles_mod.SPILLED and job.handle.id in self._handles:
                    self._host_store[job.handle.id] = host
                    installed = True
        if not installed:
            self._staging.release(host)  # a join/free won the race
        job.event.set()
        if on_ring and sess is not None:
            sess.stats.record_spill_copy(wall_ns, min(max(busy1 - busy0, 0), wall_ns))

    def refill(self, h: AlMatrix) -> None:
        """Re-place a spilled matrix on its session's worker group. Runs on
        the first consumption after the spill (``AlMatrix.data()``); may
        itself spill other matrices to make room. Atomic under the governor
        lock, like spill's transition.

        Two paths:

        - **join**: the victim's copy-out is still in flight, so its bytes
          never left the device — cancel the job and restore the retained
          device reference. Zero copies, and crucially zero *waiting*: refill
          runs with the governor lock held (``data()``), and blocking here on
          the transfer thread (which needs the lock to finish) would deadlock.
        - **replay**: ``device_put`` the host payload back through the
          session's cached relayout plan. The staging buffer is passed to the
          plan directly (no intermediate ``jnp.asarray`` device bounce) with
          the final put marked donatable, and a pool-owned buffer is donated
          back to the staging pool afterwards — one host copy saved per
          spill/refill cycle. Exception: on CPU backends the sharded/donated
          put is *zero-copy* (the placed array's backing store IS the host
          buffer), so a buffer the new device array aliases is dropped from
          the pool instead — recycling it would let a later spill's gather
          write a victim's bytes through the alias into this live matrix.
        """
        with self._lock:
            sess = self._sessions.get(h.session_id)
            job = self._in_flight.get(h.id)
            if job is not None and job.array is not None:
                # Join the pending copy: take back the device reference.
                arr = job.array
                job.array = None
                self._in_flight_bytes -= job.nbytes
                self._in_flight.pop(h.id, None)
                job.state = "cancelled"
                job.event.set()
                self.admit(job.nbytes, exclude={h.id})
                h._data = arr
                h._state = handles_mod.MATERIALIZED
                self.settle(job.nbytes)  # claim -> charge, atomic: lock held
                self.charge(h)
                nbytes_refilled = job.nbytes
                fused = False
            else:
                host = self._host_store.get(h.id)
                if host is None:
                    host = h._host_fallback
                if host is None or sess is None:
                    raise HandleError(
                        f"AlMatrix {h.id} ({h.name!r}) has no spilled payload to refill"
                    )
                # Claim exactly what charge(h) will land: the *physical*
                # extent (a logical store payload gains divisibility pads at
                # placement) priced at the handle's declared dtype. Claiming
                # host.nbytes would under-admit by the pad bytes and silently
                # overshoot the budget at the charge.
                pr, pc = pad_amounts(tuple(host.shape), h.layout, sess.mesh)
                claim = (
                    (host.shape[0] + pr)
                    * (host.shape[1] + pc)
                    * jnp.dtype(h.dtype).itemsize
                )
                self.admit(claim, exclude={h.id})
                # Host-store payloads are the *physical* (already padded,
                # already permuted) form and store fallbacks the logical one;
                # either way src == dst, so the cached plan is a pure
                # placement — no permutation, and pads exactly when the
                # payload needs them for the device_put. The put consumes the
                # host buffer directly (a slab payload slab by slab, see
                # ``payload.place``); only a dtype the device would
                # canonicalize anyway (f64 without x64 mode) is converted
                # host-side first, so the plan key matches the placed array.
                canon = jax.dtypes.canonicalize_dtype(host.dtype)
                plan, _hit = sess.relayout_cache.plan(
                    tuple(host.shape), canon, h.layout, h.layout, sess.mesh
                )
                arr, fused = place(host, plan, donate=True)
                h._data = arr
                h.pads = (arr.shape[0] - h.shape[0], arr.shape[1] - h.shape[1])
                h._state = handles_mod.MATERIALIZED
                popped = self._host_store.pop(h.id, None)
                if popped is not None and not aliases_host(arr, popped):
                    self._staging.release(popped)  # refused if client-escaped
                self.settle(claim)  # claim -> charge, atomic: lock is held
                self.charge(h)
                nbytes_refilled = int(host.nbytes)
        stats = self._stats_for(h)
        if stats is not None:
            stats.record_refill(nbytes_refilled)
            if fused:
                stats.record_fused_relayout()

    def host_payload(self, h: AlMatrix, timeout: float = 120.0) -> Optional[np.ndarray]:
        """The spilled payload (physical from the host store, or the store
        entry's logical fallback), or None if ``h`` is not spilled. Lets the
        collect path serve client-bound bytes straight from host memory — no
        refill, no admission cascade — while the handle stays spilled for any
        later engine-side consumption.

        If the spill's copy-out is still in flight, joins it by waiting on
        the job's event *outside* the governor lock (the transfer thread
        needs the lock to install the payload). A pool-owned buffer is marked
        read-only before it escapes: collects may serve it zero-copy to the
        client, so it must never be recycled for a later spill's gather.
        """
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                if h.state != handles_mod.SPILLED:
                    return None
                host = self._host_store.get(h.id)
                if host is not None:
                    if host.flags.writeable:
                        host.flags.writeable = False  # escaped: never recycle
                    return host
                if h._host_fallback is not None:
                    return h._host_fallback
                job = self._in_flight.get(h.id)
                if job is None:
                    return None
            remaining = deadline - time.monotonic()
            if remaining <= 0 or not job.event.wait(remaining):
                raise HandleError(
                    f"AlMatrix {h.id} ({h.name!r}) spill copy-out did not land "
                    f"within {timeout}s"
                )

    # -- introspection -------------------------------------------------------
    def spilled_handles(self) -> List[AlMatrix]:
        with self._lock:
            return [h for h in self._handles.values() if h.state == handles_mod.SPILLED]

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {
                "budget": self.budget or 0,
                "used": self._used,
                "reserved": self._reserved,
                "high_water": self.high_water,
                "sessions": len(self._sessions),
                "resident_handles": sum(
                    1
                    for h in self._handles.values()
                    if h.state == handles_mod.MATERIALIZED
                ),
                "spilled_handles": sum(
                    1
                    for h in self._handles.values()
                    if h.state == handles_mod.SPILLED
                ),
                "host_store_bytes": sum(a.nbytes for a in self._host_store.values()),
                "in_flight_spill_bytes": self._in_flight_bytes,
                "staging_reuses": self._staging.reuses,
                "shared_views": len(self._view_sources),
            }

    def clear(self) -> None:
        """Engine teardown: drop every charge and host-store payload, cancel
        in-flight copy-outs, and stop the transfer ring (it is rebuilt lazily
        if the governor spills again)."""
        with self._lock:
            for job in self._in_flight.values():
                if job.array is not None:
                    job.array = None
                    self._in_flight_bytes -= job.nbytes
                job.state = "cancelled"
                job.event.set()
            self._in_flight.clear()
            self._in_flight_bytes = 0
            transfer, self._transfer = self._transfer, None
            self._handles.clear()
            self._charged.clear()
            self._host_store.clear()
            self._touch.clear()
            self._pin_counts.clear()
            self._idle.clear()
            self._view_sources.clear()
            self._gated = False
            self._staging.clear()
            self._used = 0
            self._reserved = 0
        if transfer is not None:
            transfer.close(wait=True, timeout=10.0)

    def _stats_for(self, h: AlMatrix):
        sess = self._sessions.get(h.session_id)
        return sess.stats if sess is not None else None

    def _record_high_water(self, h: AlMatrix) -> None:
        # caller holds self._lock; per-session stats see the engine-wide
        # usage at their own charge moments, self.high_water the global max
        self.high_water = max(self.high_water, self._used)
        stats = self._stats_for(h)
        if stats is not None:
            stats.record_hbm_usage(self._used)

    def __repr__(self) -> str:
        s = self.snapshot()
        return (
            f"MemoryGovernor(budget={s['budget']}, used={s['used']}, "
            f"sessions={s['sessions']}, resident={s['resident_handles']}, "
            f"spilled={s['spilled_handles']})"
        )
