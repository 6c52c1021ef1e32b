"""Sessions — per-client worker groups, handle tables, and transfer stats.

Paper §2.4/§3.2: each connected Spark application gets a dedicated worker
group (its own MPI communicator spanning the Alchemist driver plus the
allocated workers), its own loaded libraries, and its own matrix namespace.
Here a worker group is a **mesh slice**: a contiguous block of the engine's
devices arranged as a ('data','model') grid.

Each session additionally owns (DESIGN.md §3):

- a :class:`~repro.core.taskqueue.TaskQueue` — the single-worker FIFO that
  executes this session's send/run/collect tasks, keeping per-application
  ordering while letting distinct sessions overlap;
- a :class:`~repro.core.relayout.RelayoutPlanCache` — memoized shard
  geometry for repeated same-shape transfers, with hit/miss counters
  surfaced through :class:`SessionStats`.

Two engine-scoped services are *viewed* rather than owned (DESIGN.md §7/§8):

- ``session.memgov`` is the **engine-wide** memory governor — one shared HBM
  byte budget across every connected session; this session's requested
  budget folds into the shared ceiling while it lives;
- ``session.residents`` is the engine's content-addressed
  :class:`~repro.core.resident.ResidentStore`. Store-backed entries in the
  handle table are per-session *placements* that pin store entries; freeing
  one unpins it, and closing the session migrates uniquely-referenced
  content to the host side instead of dropping it.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
from typing import Any, Deque, Dict, List, Optional

import jax
from jax.sharding import Mesh

from repro.core.errors import HandleError, SessionError
from repro.core import handles as handles_mod
from repro.core.handles import AlMatrix
from repro.core.layouts import LayoutSpec
from repro.core.memgov import MemoryGovernor
from repro.core.registry import Library
from repro.core.relayout import RelayoutPlanCache, TransferRecord
from repro.core.resident import ResidentStore
from repro.core.taskqueue import TaskQueue

_SESSION_IDS = itertools.count(1)

#: How many of the latest transfers a session keeps for inspection; the
#: counters above it cover the session's whole life.
TRANSFER_LOG = 256


@dataclasses.dataclass
class SessionStats:
    """Send/Compute/Receive accounting — the paper's Table 1 columns."""

    send_bytes: int = 0
    send_seconds: float = 0.0
    recv_bytes: int = 0
    recv_seconds: float = 0.0
    compute_seconds: float = 0.0
    num_sends: int = 0
    num_receives: int = 0
    num_runs: int = 0
    relayout_cache_hits: int = 0
    relayout_cache_misses: int = 0
    # Lazy offload planner counters (DESIGN.md §6): crossings the planner
    # avoided relative to a naive send→run→collect round-trip execution.
    elided_crossings: int = 0  # collect+resend round trips never performed
    resident_reuses: int = 0  # sends satisfied from this session's residents
    planned_ops: int = 0  # routine invocations lowered by the planner
    cse_hits: int = 0  # structurally identical RunExprs memoized (DESIGN.md §8)
    # Engine resident-store counters (DESIGN.md §8): sends satisfied from
    # content another session (or a closed one) already placed on the engine
    # — an attach-only placement, zero bytes over the client bridge.
    cross_session_reuses: int = 0
    # Placement-scheduler counters (DESIGN.md §12): engine-side bytes moved
    # to place this session's attaches, and attaches served as zero-byte
    # views over a shared worker group's existing placement.
    placement_bytes: int = 0
    shared_views: int = 0
    # Memory-governor counters (DESIGN.md §7): budgeted residency.
    spills: int = 0  # resident matrices moved to the pinned host store
    refills: int = 0  # spilled matrices transparently re-placed on device
    spilled_bytes: int = 0  # cumulative bytes spilled to host
    refilled_bytes: int = 0  # cumulative bytes refilled to device
    hbm_high_water: int = 0  # max engine-wide charged bytes seen at a charge
    # Asynchronous data-plane counters (DESIGN.md §10).
    spill_copy_ns: int = 0  # wall ns of async (ring) spill copy-outs
    spill_overlap_ns: int = 0  # of those, ns the queue worker was computing
    transfer_queue_depth: int = 0  # max transfer-ring depth observed at submit
    fused_relayouts: int = 0  # pad/strip ops served by the fused Pallas kernel
    transfers: Deque[TransferRecord] = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=TRANSFER_LOG)
    )

    def record_transfer(self, rec: TransferRecord) -> None:
        self.transfers.append(rec)
        if rec.planned:  # host-store-served transfers never used a plan
            if rec.cache_hit:
                self.relayout_cache_hits += 1
            else:
                self.relayout_cache_misses += 1
        if rec.fused:
            self.fused_relayouts += 1
        if rec.direction == "send":
            self.send_bytes += rec.cost.bytes_total
            self.send_seconds += rec.seconds
            self.num_sends += 1
        else:
            self.recv_bytes += rec.cost.bytes_total
            self.recv_seconds += rec.seconds
            self.num_receives += 1

    def record_compute(self, seconds: float) -> None:
        self.compute_seconds += seconds
        self.num_runs += 1

    def record_elision(self, n: int = 1) -> None:
        self.elided_crossings += n

    def record_resident_reuse(self, n: int = 1) -> None:
        self.resident_reuses += n

    def record_cross_session_reuse(self, n: int = 1) -> None:
        self.cross_session_reuses += n

    def record_placement_bytes(self, nbytes: int) -> None:
        """Engine-side device_put bytes spent placing an attach."""
        self.placement_bytes += int(nbytes)

    def record_shared_view(self, n: int = 1) -> None:
        """An attach served as a zero-byte view over a shared group."""
        self.shared_views += n

    def record_cse_hit(self, n: int = 1) -> None:
        self.cse_hits += n

    def record_planned_op(self, n: int = 1) -> None:
        self.planned_ops += n

    def record_spill(self, nbytes: int) -> None:
        self.spills += 1
        self.spilled_bytes += int(nbytes)

    def record_refill(self, nbytes: int) -> None:
        self.refills += 1
        self.refilled_bytes += int(nbytes)

    def record_hbm_usage(self, used_bytes: int) -> None:
        self.hbm_high_water = max(self.hbm_high_water, int(used_bytes))

    def record_spill_copy(self, wall_ns: int, overlap_ns: int) -> None:
        """One async copy-out finished: ``wall_ns`` of D2H, of which
        ``overlap_ns`` were hidden behind the queue worker's compute."""
        self.spill_copy_ns += int(wall_ns)
        self.spill_overlap_ns += int(overlap_ns)

    def record_transfer_depth(self, depth: int) -> None:
        self.transfer_queue_depth = max(self.transfer_queue_depth, int(depth))

    def record_fused_relayout(self, n: int = 1) -> None:
        self.fused_relayouts += n

    def summary(self) -> Dict[str, Any]:
        return {
            "send_bytes": self.send_bytes,
            "send_seconds": round(self.send_seconds, 6),
            "compute_seconds": round(self.compute_seconds, 6),
            "recv_bytes": self.recv_bytes,
            "recv_seconds": round(self.recv_seconds, 6),
            "num_sends": self.num_sends,
            "num_receives": self.num_receives,
            "num_runs": self.num_runs,
            "relayout_cache_hits": self.relayout_cache_hits,
            "relayout_cache_misses": self.relayout_cache_misses,
            "elided_crossings": self.elided_crossings,
            "resident_reuses": self.resident_reuses,
            "cross_session_reuses": self.cross_session_reuses,
            "placement_bytes": self.placement_bytes,
            "shared_views": self.shared_views,
            "cse_hits": self.cse_hits,
            "planned_ops": self.planned_ops,
            "spills": self.spills,
            "refills": self.refills,
            "spilled_bytes": self.spilled_bytes,
            "refilled_bytes": self.refilled_bytes,
            "hbm_high_water": self.hbm_high_water,
            "spill_copy_ns": self.spill_copy_ns,
            "spill_overlap_ns": self.spill_overlap_ns,
            "transfer_queue_depth": self.transfer_queue_depth,
            "fused_relayouts": self.fused_relayouts,
        }


class Session:
    """One client application's state on the engine."""

    def __init__(
        self,
        name: str,
        mesh: Mesh,
        worker_devices: List[jax.Device],
        hbm_budget: Optional[int] = None,
        memgov: Optional[MemoryGovernor] = None,
        residents: Optional[ResidentStore] = None,
    ):
        self.id = next(_SESSION_IDS)
        self.name = name
        self.mesh = mesh
        self.worker_devices = worker_devices
        # The resolved PlacementTicket (DESIGN.md §12), set by
        # AlchemistEngine.connect; None for sessions built without a
        # scheduler (unit tests, standalone).
        self.placement = None
        self.handles: Dict[int, AlMatrix] = {}
        self.libraries: Dict[str, Library] = {}
        # name -> import-path spec ("pkg.mod:Class") for every library whose
        # registration is wire-expressible — the re-admission record a fleet
        # recovery needs to rebuild the library table on another engine.
        self.library_specs: Dict[str, str] = {}
        self.stats = SessionStats()
        # The engine-wide governor (one shared budget across sessions); a
        # private one is built only for standalone/unit-test sessions.
        # Attached before the task queue exists: a rejected budget must fail
        # the constructor without leaving a live worker thread behind.
        self._owns_memgov = memgov is None
        self.memgov = memgov if memgov is not None else MemoryGovernor(name=f"memgov-{self.id}")
        self.memgov.attach_session(self, hbm_budget=hbm_budget)
        self.tasks = TaskQueue(name=f"session-{self.id}")
        self.relayout_cache = RelayoutPlanCache()
        # The engine's content-addressed resident store (None when this
        # session was built without an engine).
        self.residents = residents
        self.closed = False

    # -- handle table -------------------------------------------------------
    def new_handle(
        self,
        data: jax.Array,
        layout: LayoutSpec,
        name: str = "",
    ) -> AlMatrix:
        """Register an already-resident array (a routine output: born
        unpadded, so logical shape == physical shape — padded sends go
        through new_pending_handle + materialize(pads=...) instead) and
        charge it against the engine's HBM budget."""
        self._check_open()
        h = AlMatrix(
            shape=tuple(data.shape),
            dtype=data.dtype,
            layout=layout,
            session_id=self.id,
            name=name,
            _data=data,
        )
        self.handles[h.id] = h
        self.memgov.charge(h)
        return h

    def new_pending_handle(
        self,
        shape,
        dtype,
        layout: LayoutSpec,
        name: str = "",
    ) -> AlMatrix:
        """Register a handle whose data a queued task will materialize.

        Metadata (shape/dtype/layout) is known immediately — the paper's
        AlMatrix proxies carry exactly this before any bytes move — so the
        client can pack the handle into parameter frames and chain further
        async calls without waiting for the transfer.
        """
        self._check_open()
        h = AlMatrix(
            shape=tuple(int(d) for d in shape),
            dtype=jax.numpy.dtype(dtype),
            layout=layout,
            session_id=self.id,
            name=name,
            _state=handles_mod.PENDING,
            _governor=self.memgov,
        )
        self.handles[h.id] = h
        return h

    def get_handle(self, handle_id: int) -> AlMatrix:
        self._check_open()
        try:
            return self.handles[handle_id]
        except KeyError:
            raise HandleError(
                f"session {self.id} has no AlMatrix with id {handle_id}"
            ) from None

    def resolve(self, h: AlMatrix) -> AlMatrix:
        """Validate a client-held handle belongs to this session and is live."""
        self._check_open()
        if h.session_id != self.id:
            raise HandleError(
                f"AlMatrix {h.id} belongs to session {h.session_id}, not {self.id} "
                "(handles are not shareable across applications)"
            )
        if h.id not in self.handles:
            raise HandleError(f"AlMatrix {h.id} is not registered in session {self.id}")
        return self.handles[h.id]

    def free_handle(self, h: AlMatrix) -> None:
        live = self.resolve(h)
        live.free()
        if live.store_key is not None and self.residents is not None:
            # An explicit free unpins the store entry; with its last pin the
            # content is gone for good (unlike a close, which migrates).
            self.residents.release(live.store_key, self.id, live)
        del self.handles[live.id]

    # -- lifecycle ----------------------------------------------------------
    def drain(self, timeout: Optional[float] = None) -> None:
        """Barrier: wait until every queued task of this session finished."""
        self.tasks.barrier(timeout)

    def close(self) -> None:
        if self.closed:
            return
        self.tasks.close(wait=True, timeout=60.0)
        # Store-backed placements first: uniquely-referenced content migrates
        # to the host side (DESIGN.md §8) instead of dying with the session.
        if self.residents is not None:
            self.residents.detach_session(self)
        for h in list(self.handles.values()):
            if h.state != handles_mod.FREED:
                h.free()
        self.handles.clear()
        self.libraries.clear()
        if self._owns_memgov:
            self.memgov.clear()
        self.memgov.detach_session(self.id)
        self.closed = True

    def _check_open(self) -> None:
        if self.closed:
            raise SessionError(f"session {self.id} ({self.name!r}) is closed")

    @property
    def num_workers(self) -> int:
        return len(self.worker_devices)

    def descriptor(self) -> Dict[str, Any]:
        """JSON-serializable re-admission record (DESIGN.md §14).

        Everything a fleet recovery needs to re-admit this session on
        another engine through the queued ``connect(placement=...)`` path:
        the placement shape actually granted (workers/grid/priority) and the
        wire-expressible library specs. Data and computation are
        deliberately absent — residents travel by content key through the
        store, and lost outputs re-enter via lineage replay of the client's
        expr DAG.
        """
        t = self.placement
        return {
            "session_id": int(self.id),
            "name": self.name,
            "workers": int(self.num_workers),
            "grid": [int(d) for d in self.mesh.devices.shape],
            "priority": int(t.priority) if t is not None else 0,
            "allow_shared": bool(t.allow_shared) if t is not None else True,
            "libraries": dict(self.library_specs),
        }

    def __repr__(self) -> str:
        return (
            f"Session(id={self.id}, name={self.name!r}, workers={self.num_workers}, "
            f"grid={tuple(self.mesh.devices.shape)}, handles={len(self.handles)})"
        )
