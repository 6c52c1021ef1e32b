"""AlchemistEngine — the server: device pool, sessions, admission control.

Paper §2/§3: Alchemist runs as a driver + worker-pool server; a client
application connects, requests a number of workers, and gets a dedicated
worker group. TPU adaptation (DESIGN.md §2): the worker pool is the device
set of a mesh; a worker group is a mesh slice; the socket transfer is a
relayout; ``dlopen`` is import-by-path.

The client side lives in :mod:`repro.core.client` (DESIGN.md §9): the v2
``connect()``/:class:`~repro.core.client.Session`/:class:`AlArray` surface,
with the v1 :class:`~repro.core.client.AlchemistContext` kept as a
deprecation shim over the same transport core.

Since PR 8 all admission flows through the unified placement scheduler
(DESIGN.md §12): callers describe what they need with a declarative
:class:`~repro.core.scheduler.PlacementRequest` (workers, priority, content
affinity, deadline, shareability) and the engine-owned
:class:`~repro.core.scheduler.PlacementScheduler` turns it into a
:class:`~repro.core.scheduler.PlacementTicket` — a FIFO queue entry with
smallest-fit + content-affinity scoring, anti-starvation aging, pressure
watermarks over ``memgov.pressure()``, and refcounted shared worker groups.
The v1 kwargs (``queue=``, ``timeout=``, ``datasets=``) keep working through
a deprecation shim that folds them into a request.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.errors import WorkerAllocationError
from repro.core.expr import content_key
from repro.core.layouts import AXIS_DATA, AXIS_MODEL
from repro.core.memgov import MemoryGovernor
from repro.core.resident import ResidentStore
from repro.core.scheduler import (
    PlacementRequest,
    PlacementScheduler,
    PlacementTicket,
    near_square_grid as _near_square_grid,  # noqa: F401  (legacy import site)
)
from repro.core.session import Session

# Sentinel distinguishing "kwarg not passed" from an explicit None/() on the
# deprecated v1 admission kwargs.
_UNSET = object()


def _dataset_keys(datasets: Sequence[Any]) -> List[Tuple]:
    """Normalize declared datasets to resident-store content keys.

    Accepts precomputed key tuples, host/device arrays (hashed here), and
    deferred send nodes (an :class:`~repro.core.client.AlArray`/LazyMatrix
    over a SendExpr, whose key was computed at graph build). A *derived*
    expression (a routine output) has no content identity until it executes
    — declaring one is rejected rather than silently hashed to garbage."""
    keys: List[Tuple] = []
    for d in datasets:
        if isinstance(d, tuple):
            keys.append(d)
            continue
        node = getattr(d, "expr", None)
        if node is not None:
            node_key = getattr(node, "key", None)
            if node_key:
                keys.append(node_key)
                continue
            raise WorkerAllocationError(
                "declared dataset is a derived expression with no content key; "
                "declare the source array (or its send node) instead"
            )
        if isinstance(d, (np.ndarray, jax.Array)):
            keys.append(content_key(d))
            continue
        raise WorkerAllocationError(
            f"cannot derive a content key from declared dataset {type(d).__name__}"
        )
    return keys


def _coerce_request(
    placement: Optional[PlacementRequest],
    num_workers: Optional[int] = None,
    grid: Optional[Tuple[int, int]] = None,
    datasets: Any = _UNSET,
    queue: Any = _UNSET,
    timeout: Any = _UNSET,
) -> PlacementRequest:
    """Fold v1 admission kwargs into a :class:`PlacementRequest`.

    ``workers``/``grid`` remain first-class sugar (no warning); the v1
    admission trio (``datasets``/``queue``/``timeout``) warns and maps onto
    ``affinity``/``deadline`` per the DESIGN.md §12 migration table.
    """
    legacy = [
        name
        for name, value in (("datasets", datasets), ("queue", queue), ("timeout", timeout))
        if value is not _UNSET
    ]
    if legacy:
        warnings.warn(
            f"{', '.join(legacy)} kwarg(s) are deprecated; pass "
            "placement=PlacementRequest(affinity=..., deadline=...) instead "
            "(DESIGN.md §12 migration table)",
            DeprecationWarning,
            stacklevel=3,
        )
    if placement is not None:
        if num_workers is not None or grid is not None or legacy:
            raise WorkerAllocationError(
                "pass either placement=PlacementRequest(...) or the legacy "
                "workers/grid/datasets/queue/timeout kwargs, not both"
            )
        return placement
    queue = False if queue is _UNSET else bool(queue)
    timeout = None if timeout is _UNSET else timeout
    datasets = () if datasets is _UNSET else datasets
    # v1 deadline semantics: queue=False fails fast regardless of timeout;
    # queue=True waits for `timeout` seconds (None = indefinitely).
    deadline = (None if timeout is None else float(timeout)) if queue else 0.0
    return PlacementRequest(
        workers=num_workers,
        grid=grid,
        affinity=tuple(datasets),
        deadline=deadline,
    )


class AlchemistEngine:
    """The Alchemist server: owns the worker (device) pool, hands out
    sessions with dedicated worker-group mesh slices, and holds the
    engine-scoped services every session shares (DESIGN.md §7/§8/§12):

    - ``memgov`` — the engine-wide memory governor. ``hbm_budget`` caps the
      *combined* resident footprint of all sessions (each session may lower
      the shared ceiling further via a per-session ``hbm_budget``);
      ``pressure_watermarks=(high, low)`` — fractions of the effective
      budget — additionally gate new private placements on governor
      pressure, with hysteresis (block above high, resume below low);
    - ``residents`` — the content-addressed resident store that dedups
      byte-identical sends across sessions and migrates uniquely-referenced
      content host-side when its session stops. ``share_residents=False``
      restores the session-scoped baseline (every session ships its own
      copy); ``host_retention_bytes`` bounds migrated-content host memory;
    - ``scheduler`` — the unified placement scheduler: FIFO ticket queue
      with smallest-fit + content-affinity scoring, an ``aging_bound``
      anti-starvation barrier, and refcounted shared worker groups.
    """

    def __init__(
        self,
        devices: Optional[Sequence[jax.Device]] = None,
        name: str = "alchemist",
        hbm_budget: Optional[int] = None,
        share_residents: bool = True,
        host_retention_bytes: Optional[int] = None,
        async_spill: bool = True,
        aging_bound: int = 4,
        pressure_watermarks: Optional[Tuple[float, float]] = None,
    ):
        self.name = name
        self.devices: List[jax.Device] = list(devices if devices is not None else jax.devices())
        if not self.devices:
            raise WorkerAllocationError("engine started with an empty device pool")
        self.sessions: Dict[int, Session] = {}
        # async_spill=False restores the synchronous copy-out baseline —
        # benchmarks/overlap_spill.py uses it as the numerics control.
        self.memgov = MemoryGovernor(
            budget=hbm_budget, name=f"{name}-memgov", async_spill=async_spill
        )
        if pressure_watermarks is not None:
            high, low = pressure_watermarks
            self.memgov.set_watermarks(high, low)
        self.residents = ResidentStore(enabled=share_residents, retain_bytes=host_retention_bytes)
        self.scheduler = PlacementScheduler(
            self.devices,
            memgov=self.memgov,
            residents=self.residents,
            aging_bound=aging_bound,
        )
        # Supervision anchors: wall-clock birth for operators, a monotonic
        # origin for drift-free uptime, and a snapshot sequence number so a
        # fleet scraper can reject stale or reordered stats replies.
        self.started_at = time.time()
        self._monotonic_start = time.monotonic()
        self._snapshot_seq = 0

    # -- worker allocation ---------------------------------------------------
    @property
    def num_workers(self) -> int:
        return len(self.devices)

    @property
    def available_workers(self) -> int:
        return len(self.scheduler.free_devices)

    @property
    def queued_connects(self) -> int:
        """Admission tickets currently waiting in the scheduler queue."""
        return self.scheduler.queued

    @property
    def admissions(self) -> Dict[str, Any]:
        """The scheduler's externally-visible admission counters."""
        return self.scheduler.admissions

    @property
    def _free(self) -> List[jax.Device]:
        """Free pool in canonical order (kept readable for legacy probes)."""
        return self.scheduler.free_devices

    def _submit(self, request: PlacementRequest) -> PlacementTicket:
        """Resolve affinity to content keys and queue the request."""
        affinity = request.affinity or ()
        # Hash declared datasets only when affinity can actually apply — the
        # signal is discarded with the store disabled, and content_key reads
        # every byte of every declared array.
        keys = _dataset_keys(affinity) if affinity and self.residents.enabled else []
        return self.scheduler.submit(request, keys=keys)

    def _mesh_for(self, ticket: PlacementTicket) -> Mesh:
        rows, cols = ticket.grid
        return Mesh(
            np.asarray(ticket.devices, dtype=object).reshape(rows, cols),
            (AXIS_DATA, AXIS_MODEL),
        )

    def allocate(
        self,
        num_workers: Optional[int] = None,
        grid: Optional[Tuple[int, int]] = None,
        *,
        datasets: Any = _UNSET,
        queue: Any = _UNSET,
        timeout: Any = _UNSET,
        placement: Optional[PlacementRequest] = None,
    ) -> Tuple[Mesh, List[jax.Device]]:
        """Carve a worker group out of the free pool.

        v2 callers pass ``placement=PlacementRequest(...)``; the positional
        ``num_workers``/``grid`` remain sugar for a fail-fast private request
        and the v1 ``datasets``/``queue``/``timeout`` kwargs warn and fold
        into the request. Raw allocations are always *private* (no shared
        group can outlive an unbound device list) and the caller owns
        returning the devices. Prefer :meth:`connect`, which binds the
        placement to a session for refcounted release.
        """
        request = _coerce_request(placement, num_workers, grid, datasets, queue, timeout)
        if request.allow_shared:
            request = dataclasses.replace(request, allow_shared=False)
        ticket = self._submit(request)
        self.scheduler.orphan(ticket)
        return self._mesh_for(ticket), list(ticket.devices)

    def _pick_block(self, n: int, keys: Sequence[Tuple]) -> List[jax.Device]:
        """Legacy scoring probe: choose ``n`` free devices without consuming
        them (DESIGN.md §12 smallest-fit + content-affinity scoring)."""
        usable_keys = list(keys) if (keys and self.residents.enabled) else []
        return self.scheduler.pick_block(n, usable_keys)

    def release(self, session: Session) -> None:
        owned = self.sessions.pop(session.id, None) is not None
        # Drain the session's task queue BEFORE the devices go back in the
        # pool: a concurrent connect() must never be handed devices whose old
        # session still has tasks dispatching (disjoint worker groups, §2.4).
        session.close()
        if owned:
            # The scheduler drops a group refcount; the pool is restored in
            # canonical device order only when the last reader leaves.
            self.scheduler.release_session(session.id, session.worker_devices)

    def connect(
        self,
        name: str = "app",
        num_workers: Optional[int] = None,
        grid: Optional[Tuple[int, int]] = None,
        hbm_budget: Optional[int] = None,
        *,
        placement: Optional[PlacementRequest] = None,
        datasets: Any = _UNSET,
        queue: Any = _UNSET,
        timeout: Any = _UNSET,
    ) -> Session:
        request = _coerce_request(placement, num_workers, grid, datasets, queue, timeout)
        ticket = self._submit(request)
        try:
            session = Session(
                name=name,
                mesh=self._mesh_for(ticket),
                worker_devices=list(ticket.devices),
                hbm_budget=hbm_budget,
                memgov=self.memgov,
                residents=self.residents,
            )
        except BaseException:
            # A rejected session (e.g. an invalid budget) must hand its
            # placement straight back — refcounted, so a shared join merely
            # drops the reader count.
            self.scheduler.abort(ticket)
            raise
        session.placement = ticket
        self.scheduler.bind(ticket, session.id)
        self.sessions[session.id] = session
        return session

    # -- observability -------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """One merged engine snapshot (DESIGN.md §9/§12): the worker pool and
        admission queue, every live session's ``SessionStats`` (plus its
        resolved placement ticket and its task queue's counters), the
        engine-wide governor (``pressure()``, budget, high water), the
        resident store, and the scheduler section
        (queue depth, ticket lifecycle counters, shared groups, scoring
        hits). This is what ``benchmarks/run.py --json`` embeds."""
        self._snapshot_seq += 1
        pool = {
            "workers": self.num_workers,
            "available_workers": self.available_workers,
            "queued_connects": self.queued_connects,
            "live_sessions": len(self.sessions),
            "admissions": dict(self.admissions),
            "started_at": self.started_at,
            "uptime_s": time.monotonic() - self._monotonic_start,
            "snapshot_seq": self._snapshot_seq,
        }
        sessions = dict(self.sessions)
        mg = self.memgov
        return {
            "engine": pool,
            "sessions": {
                str(sid): {
                    "name": s.name,
                    "workers": s.num_workers,
                    "placement": (
                        s.placement.summary() if s.placement is not None else None
                    ),
                    **s.stats.summary(),
                    "tasks": s.tasks.stats(),
                }
                for sid, s in sessions.items()
            },
            "memgov": {
                "pressure": mg.pressure(),
                "used": mg.used,
                "reserved": mg.reserved,
                "high_water": mg.high_water,
                "budget": mg.budget,
            },
            "residents": self.residents.stats(),
            "scheduler": self.scheduler.stats(),
            "wire": self._wire_stats(),
        }

    def _wire_stats(self) -> Dict[str, Any]:
        """The v2 data-plane section (DESIGN.md §13): the engine's wire
        server counters when one is live, zeros otherwise — always the same
        JSON-serializable shape so dashboards key on it unconditionally."""
        from repro.serve.wire import server_for  # lazy: serve imports core

        srv = server_for(self)
        if srv is None:
            return {
                "server": False,
                "inflight": 0,
                "max_inflight": 0,
                "vectored_writes": 0,
                "shard_direct_receives": 0,
                "reassembly_receives": 0,
                "streamed_fetches": 0,
                "gathered_fetches": 0,
                "overlap_ns": 0,
                "put_ns": 0,
                "version_rejects": 0,
                "bytes_in": 0,
                "bytes_out": 0,
            }
        st = srv.stats
        return {
            "server": True,
            "inflight": srv.inflight_depth(),
            "max_inflight": int(st["max_inflight"]),
            "vectored_writes": int(st["vectored_writes"]),
            "shard_direct_receives": int(st["shard_direct_receives"]),
            "reassembly_receives": int(st["reassembly_receives"]),
            "streamed_fetches": int(st["streamed_fetches"]),
            "gathered_fetches": int(st["gathered_fetches"]),
            "overlap_ns": int(st["overlap_ns"]),
            "put_ns": int(st["put_ns"]),
            "version_rejects": int(st["version_rejects"]),
            "bytes_in": int(st["bytes_in"]),
            "bytes_out": int(st["bytes_out"]),
        }

    def shutdown(self) -> None:
        """Stop every session and drop engine-wide state (the resident
        store's migrated content and the governor's ledger)."""
        for session in list(self.sessions.values()):
            self.release(session)
        self.residents.clear()
        self.memgov.clear()


# Backwards-compatible re-exports: the client surface lived in this module
# until DESIGN.md §9 split it out. Imported late to keep the module graph
# acyclic (client.py never imports engine.py at runtime).
from repro.core.client import AlchemistContext  # noqa: E402,F401
