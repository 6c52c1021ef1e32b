"""ResidentStore — the engine-level content-addressed resident-matrix index.

DESIGN.md §8. The Alchemist papers stress that the server amortizes data
movement *across* clients: several Spark/Dask applications connect to one
Alchemist instance and share its worker-side matrices (arXiv:1805.11800,
arXiv:1910.01354). Until this layer existed, both the resident-matrix cache
(§6) and the memory governor (§7) were session-scoped — two sessions sending
the same dataset shipped it across the bridge twice and budgeted it twice.

The store lifts content identity to the engine:

- every non-cyclic send is **published** under its content key
  (:func:`repro.core.expr.content_key`): the entry records the host payload
  when it comes for free — the planner's snapshotted ``SendExpr`` array
  in-process, or, over a wire, the received staging slabs adopted as they
  are (:class:`~repro.core.payload.SlabPayload`; they go back to the
  staging pool when the entry dies) — plus one *placement* per session that
  holds the matrix on its worker group;
- a second session sending byte-identical data **attaches** instead: no
  bytes cross the client↔engine bridge — the engine already has them — and
  the session's placement is a plain engine-internal ``device_put`` from the
  entry's payload (counted as ``cross_session_reuses`` in that session's
  stats, and as ``attaches`` here);
- placements **pin** the entry: the refcount is the number of live
  placements, the session-pin set the sessions holding them. An explicit
  ``free`` unpins, and the entry dies with its last placement — exactly the
  old per-session lifecycle, observed through the store;
- when a session **closes**, its uniquely-referenced entries are *migrated*
  rather than freed: the device placement is dropped (its HBM charge with
  it), but the logical payload is kept host-side so a later session can
  refill the same content by key without ever re-crossing the bridge. The
  migration staging area is the same host-side plane the governor's spill
  store lives on (§7): ``ensure_payload`` pulls the bytes from the entry's
  snapshot, the handle's host fallback, the governor's host store, or — last
  resort — a ``device_get`` of the live placement.

Sessions therefore *view* the store: their handle tables hold per-session
placement handles (an :class:`~repro.core.handles.AlMatrix` whose
``store_key`` names the entry), and pin/unpin entries instead of owning the
content. The store is deliberately host-metadata only — device residency,
budgets, and spill/refill stay the engine-wide governor's job.

Cyclic layouts bypass the store: their resident form is a physical row
permutation of the payload, which does not round-trip through the pure
placement plan the attach/refill paths use (see ``pad_amounts``).
"""

from __future__ import annotations

import itertools
import threading
from typing import TYPE_CHECKING, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import handles as handles_mod
from repro.core.errors import HandleError, TaskError
from repro.core.handles import AlMatrix
from repro.core.payload import SlabPayload
from repro.core.transport import StagedShards

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids an import cycle
    from repro.core.session import Session

_CLOCK = itertools.count(1)


class ResidentEntry:
    """One content-addressed resident matrix: host payload + placements."""

    def __init__(self, key: Tuple, shape: Tuple[int, int], dtype, layout):
        self.key = key
        self.shape = tuple(int(d) for d in shape)
        self.dtype = dtype
        self.layout = layout
        #: logical host bytes (row-major, unpadded): an ndarray, or the
        #: adopted receive slabs (``SlabPayload``) — None until a publisher
        #: hands them over or a migration/attach fetches them.
        self.payload = None
        #: session id -> that session's placement handles (usually one).
        self.placements: Dict[int, List[AlMatrix]] = {}
        #: ids of sessions whose placement was *migrated* out (session close
        #: secured the payload host-side). Lets the fleet recovery enumerate
        #: a drained session's content after the drain already ran — explicit
        #: frees never land here (a user free means the content is done).
        self.former_sessions: set = set()
        #: ids of the worker-group devices that most recently held a
        #: placement of this content — the admission-time affinity signal
        #: (DESIGN.md §9): a later ``connect(datasets=...)`` prefers the free
        #: block these ids name, so warm content is reused in place.
        self.device_ids: frozenset = frozenset()
        self.last_use: int = next(_CLOCK)

    # -- pin accounting ------------------------------------------------------
    @property
    def refcount(self) -> int:
        """Live placements across all sessions (the entry's pin count)."""
        return sum(len(hs) for hs in self.placements.values())

    @property
    def sessions(self) -> Tuple[int, ...]:
        """The session-pin set: ids of sessions holding a placement."""
        return tuple(sorted(self.placements))

    def handles_for(self, session_id: int) -> List[AlMatrix]:
        return list(self.placements.get(session_id, ()))

    def live_handle_for(self, session_id: int) -> Optional[AlMatrix]:
        for h in self.placements.get(session_id, ()):
            if h.is_live:
                return h
        return None

    def live_handles(self) -> List[AlMatrix]:
        return [h for hs in self.placements.values() for h in hs if h.is_live]

    def usable(self) -> bool:
        """Can a new placement be produced without a bridge crossing?"""
        return self.payload is not None or bool(self.live_handles())

    def nbytes(self) -> int:
        if self.payload is not None:
            return int(self.payload.nbytes)
        n = 1
        for d in self.shape:
            n *= d
        return n * jax.numpy.dtype(self.dtype).itemsize

    def __repr__(self) -> str:
        return (
            f"ResidentEntry(shape={self.shape}, refcount={self.refcount}, "
            f"sessions={list(self.sessions)}, payload={self.payload is not None})"
        )


class ResidentStore:
    """Engine-wide content index of resident matrices (DESIGN.md §8).

    ``enabled=False`` turns every lookup into a miss and every publish into a
    no-op — the session-scoped pre-store behaviour, kept as an explicit
    baseline for benchmarks (``AlchemistEngine(share_residents=False)``).

    ``retain_bytes`` caps the host bytes held by *orphaned* entries (content
    migrated out of closed sessions, awaiting a future attach); the oldest
    orphans are evicted beyond it. ``None`` retains everything — fine for
    tests and short-lived engines, bound it for long-running servers.
    """

    def __init__(self, enabled: bool = True, retain_bytes: Optional[int] = None):
        self.enabled = enabled
        self.retain_bytes = retain_bytes
        self._entries: Dict[Tuple, ResidentEntry] = {}
        self._lock = threading.RLock()
        self.publishes = 0
        self.attaches = 0
        self.migrations = 0
        self.evictions = 0
        self.payload_adopted_bytes = 0
        self.payload_copied_bytes = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- index ---------------------------------------------------------------
    def lookup(self, key: Tuple) -> Optional[ResidentEntry]:
        """The entry for ``key`` (pruned of dead placements), or None."""
        if not self.enabled:
            return None
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            self._prune(entry)
            if not entry.usable() and entry.refcount == 0:
                # the content died everywhere (failed send, freed before any
                # payload was captured): forget it so the caller re-sends
                del self._entries[key]
                return None
            return entry

    def register(
        self,
        key: Tuple,
        handle: AlMatrix,
        session: "Session",
        payload=None,
        *,
        copy: bool = False,
    ) -> ResidentEntry:
        """Publish a (possibly still pending) placement under ``key``.

        Called by the send path for the producing session and by the attach
        path for every subsequent one; idempotent per handle. ``payload`` —
        the logical host bytes — makes later migration and cross-session
        placement free. The first one an entry sees is taken over without a
        copy: received staging slabs (``StagedShards``) are adopted as they
        lie, an ndarray is the caller's private copy (the planner's
        snapshot). Only ``copy=True`` — an array its owner may still change —
        costs a host copy. ``payload_adopted_bytes`` and
        ``payload_copied_bytes`` count the two.
        """
        if not self.enabled:
            return ResidentEntry(key, handle.shape, handle.dtype, handle.layout)
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                entry = ResidentEntry(key, handle.shape, handle.dtype, handle.layout)
                self._entries[key] = entry
                self.publishes += 1
            if payload is not None and entry.payload is None:
                entry.payload = self._take(payload, copy)
            hs = entry.placements.setdefault(session.id, [])
            if handle not in hs:
                hs.append(handle)
            handle.store_key = key
            if entry.payload is not None:
                handle._host_fallback = entry.payload
            devices = getattr(session, "worker_devices", ())
            if devices:
                entry.device_ids = frozenset(d.id for d in devices)
            entry.last_use = next(_CLOCK)
            return entry

    def _take(self, payload, copy: bool):
        # caller holds self._lock
        if copy and not isinstance(payload, StagedShards):
            with TraceAnnotation("al.host.copy", nbytes=payload.nbytes, site="payload"):
                taken = np.array(payload)
            self.payload_copied_bytes += int(taken.nbytes)
            return taken
        taken = payload.adopt() if isinstance(payload, StagedShards) else np.asarray(payload)
        self.payload_adopted_bytes += int(taken.nbytes)
        return taken

    def _drop(self, entry: ResidentEntry) -> None:
        """The entry dies: forget its key, and give adopted receive slabs
        back to the staging pool. Caller holds self._lock."""
        self._entries.pop(entry.key, None)
        if isinstance(entry.payload, SlabPayload):
            entry.payload.release()
        entry.payload = None

    def record_attach(self) -> None:
        with self._lock:
            self.attaches += 1

    def device_affinity(self, keys) -> List[frozenset]:
        """Device-id sets that last held each of the given content keys.

        The admission-time placement signal (DESIGN.md §9): only *usable*
        entries count — content that can actually produce a new placement
        without a bridge crossing (a live placement or a host payload).
        Unknown keys and dead entries contribute nothing, so a declared
        dataset the engine has never seen simply doesn't steer placement.
        """
        if not self.enabled:
            return []
        out: List[frozenset] = []
        with self._lock:
            for key in keys:
                entry = self._entries.get(key)
                if entry is None:
                    continue
                self._prune(entry)
                if entry.device_ids and entry.usable():
                    out.append(entry.device_ids)
        return out

    # -- unpin / teardown ----------------------------------------------------
    def release(self, key: Tuple, session_id: int, handle: AlMatrix) -> None:
        """Explicit free of one placement: unpin, and drop the entry with its
        last pin (a user free means "this content is done", unlike a session
        close, which migrates)."""
        if not self.enabled:
            return
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return
            hs = entry.placements.get(session_id)
            if hs is not None:
                hs[:] = [h for h in hs if h is not handle]
                if not hs:
                    del entry.placements[session_id]
            if entry.refcount == 0:
                self._drop(entry)

    def detach_session(self, session: "Session") -> int:
        """Session close: unpin every entry this session placed.

        Entries still pinned elsewhere just lose this session's placement;
        uniquely-referenced ones are **migrated** — the payload is secured
        host-side first (``ensure_payload``, staging through the governor's
        host store when the placement is spilled), then the device placement
        is freed. Returns the number of migrations.
        """
        if not self.enabled:
            return 0
        with self._lock:
            mine = [
                (entry, entry.placements.get(session.id, []))
                for entry in list(self._entries.values())
                if session.id in entry.placements
            ]
        migrated = 0
        for entry, hs in mine:
            sole = set(entry.sessions) <= {session.id}
            if sole and self.ensure_payload(entry) is not None:
                migrated += 1
            with self._lock:
                for h in hs:
                    if h.is_live:
                        h.free()  # drops the HBM charge + any spill bytes
                entry.placements.pop(session.id, None)
                if entry.payload is not None:
                    entry.former_sessions.add(session.id)
                if entry.refcount == 0 and entry.payload is None:
                    # nothing left to refill from: forget the key
                    self._entries.pop(entry.key, None)
        with self._lock:
            self.migrations += migrated
        self._enforce_retention()
        return migrated

    def clear(self) -> None:
        """Engine shutdown: drop every entry (placements were freed by their
        sessions' close)."""
        with self._lock:
            for entry in list(self._entries.values()):
                self._drop(entry)

    # -- lineage recovery (DESIGN.md §14) ------------------------------------
    def recoverable_for(self, session_id: int) -> Dict[Tuple, ResidentEntry]:
        """Content this session pinned whose host bytes can still be secured.

        The fleet recovery planner's enumeration step: for each entry the
        (dead) session holds a placement of, try ``ensure_payload`` — the
        snapshot captured at publish time, a host fallback, or the governor's
        spill store all survive an engine death because they live host-side.
        Entries whose bytes are gone everywhere are simply omitted: their
        content re-enters through lineage replay (the ``SendExpr`` that
        produced them re-runs), not through the store.
        """
        if not self.enabled:
            return {}
        with self._lock:
            mine = [
                entry
                for entry in self._entries.values()
                if session_id in entry.placements
                or session_id in entry.former_sessions
            ]
        out: Dict[Tuple, ResidentEntry] = {}
        for entry in mine:
            if self.ensure_payload(entry) is not None:
                out[entry.key] = entry
        return out

    def adopt(self, entry: ResidentEntry) -> bool:
        """Import another store's entry as an orphan: payload only, no
        placements, no pins.

        The recovery path seeds the *surviving* engine's store with the dead
        engine's secured payloads, so the re-admitted session's re-lowered
        sends take the attach path — content refills by key with zero bytes
        re-crossing the client↔engine bridge, exactly like a
        migration-on-close refill. Returns True when the payload was adopted
        (new key, or backfilled a payload-less local entry).
        """
        if not self.enabled or entry.payload is None:
            return False
        with self._lock:
            local = self._entries.get(entry.key)
            if local is None:
                local = ResidentEntry(entry.key, entry.shape, entry.dtype, entry.layout)
                self._entries[entry.key] = local
                self.publishes += 1
            adopted = local.payload is None
            if adopted:
                local.payload = entry.payload
                if isinstance(local.payload, SlabPayload):
                    local.payload.retain()  # held by both stores now
            local.last_use = next(_CLOCK)
        self._enforce_retention()
        return adopted

    # -- payload staging -----------------------------------------------------
    def ensure_payload(self, entry: ResidentEntry) -> Optional[np.ndarray]:
        """Secure the entry's logical host bytes, fetching them if needed.

        Source order: the entry's snapshot, a placement's host fallback, the
        governor's host store (a spilled placement — no refill performed),
        then a ``device_get`` of a live placement. May block on a *producer*
        placement whose transfer is still in flight (cross-session wait: the
        producer's FIFO owes no task to ours, so this cannot deadlock);
        pending **attach** placements are never used as sources — they
        consume this very payload, and waiting on one (our own, or a sibling
        session's) would deadlock the queue workers against each other.
        Returns None when the content is gone everywhere.
        """
        with self._lock:
            if entry.payload is not None:
                return entry.payload
            candidates = [
                h
                for h in entry.live_handles()
                if not (h._placement_only and h.state == handles_mod.PENDING)
            ]
        for h in candidates:
            payload = self._payload_from(h)
            if payload is not None:
                with self._lock:
                    if entry.payload is None:
                        entry.payload = payload
                    # Backfill every live placement: any of them can now
                    # spill for free (drop device bytes, no device_get) and
                    # refill from the entry instead of a private host copy.
                    for live in entry.live_handles():
                        if live._host_fallback is None:
                            live._host_fallback = entry.payload
                    return entry.payload
        return None

    @staticmethod
    def _payload_from(h: AlMatrix) -> Optional[np.ndarray]:
        if h._host_fallback is not None:
            return h._host_fallback
        gov = h._governor
        if gov is not None:
            host = gov.host_payload(h)
            if host is not None:  # spilled: physical bytes, pads still on
                return np.asarray(host[: h.shape[0], : h.shape[1]])
        try:
            return np.asarray(jax.device_get(h.data()))
        except (HandleError, TaskError):
            return None  # freed or failed under us: try the next placement

    # -- maintenance ---------------------------------------------------------
    def _prune(self, entry: ResidentEntry) -> None:
        # caller holds self._lock
        for sid in list(entry.placements):
            hs = [h for h in entry.placements[sid] if h.is_live]
            if hs:
                entry.placements[sid] = hs
            else:
                del entry.placements[sid]

    def _enforce_retention(self) -> None:
        if self.retain_bytes is None:
            return
        with self._lock:
            orphans = [
                e for e in self._entries.values() if e.refcount == 0 and e.payload is not None
            ]
            held = sum(e.nbytes() for e in orphans)
            for e in sorted(orphans, key=lambda e: e.last_use):
                if held <= self.retain_bytes:
                    break
                held -= e.nbytes()
                self._drop(e)
                self.evictions += 1

    def stats(self) -> Dict[str, int]:
        with self._lock:
            orphaned = sum(1 for e in self._entries.values() if e.refcount == 0)
            return {
                "entries": len(self._entries),
                "orphaned": orphaned,
                "pinned": len(self._entries) - orphaned,
                "payload_bytes": sum(
                    e.nbytes() for e in self._entries.values() if e.payload is not None
                ),
                "publishes": self.publishes,
                "attaches": self.attaches,
                "migrations": self.migrations,
                "evictions": self.evictions,
                "payload_adopted_bytes": self.payload_adopted_bytes,
                "payload_copied_bytes": self.payload_copied_bytes,
            }

    def snapshot(self) -> Dict[Tuple, Dict]:
        """Per-entry view for tests/debugging."""
        with self._lock:
            return {
                key: {
                    "refcount": e.refcount,
                    "sessions": list(e.sessions),
                    "payload": e.payload is not None,
                    "states": [h.state for h in e.live_handles()],
                }
                for key, e in self._entries.items()
            }

    def __repr__(self) -> str:
        s = self.stats()
        return (
            f"ResidentStore(entries={s['entries']}, pinned={s['pinned']}, "
            f"orphaned={s['orphaned']}, attaches={s['attaches']}, "
            f"migrations={s['migrations']})"
        )
