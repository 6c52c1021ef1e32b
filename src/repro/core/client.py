"""The v2 client surface: ``connect()`` → :class:`Session` → :class:`AlArray`.

DESIGN.md §9. The paper frames Alchemist's value as "minimal coding overhead"
for Spark users, yet by PR 4 the repo exposed three parallel client APIs —
eager ``ac.send/run/collect``, async ``*_async`` futures, and the planner's
``LazyMatrix`` DAG — each with its own handle type, stats, and failure
surface. v2 collapses them into one lazy-by-default API:

    import repro

    engine = repro.AlchemistEngine()
    with repro.connect(engine, workers=4) as session:
        session.register_library("elemental", "repro.linalg.library:ElementalLib")
        a = session.send(A)                               # AlArray (deferred)
        c = a @ session.send(B)                           # builds the DAG
        u, s, v = session.run("elemental", "truncated_svd", c, n_outputs=3, k=8)
        U = u.data()                                      # forces through the planner

Every operation builds an expression node; **when** nodes execute is the
session's :class:`~repro.core.policy.ExecutionPolicy` (``Eager`` /
``Pipelined`` / ``Planned``), settable per session or per ``with
session.policy(...)`` scope — never a per-call API choice. All policies run
the same DAG through the same planner, so results are bit-identical.

``connect()`` is **admission-aware** (paper §2.4's "assuming a sufficient
number of workers is available", removed): when the engine cannot place the
worker group it queues the request until a group frees up (with an optional
timeout), and placement prefers the free device block whose resident-store
content the session's *declared datasets* will reuse — see
:meth:`AlchemistEngine.allocate`.

Layering: :class:`ClientCore` is the transport (the old ``AlchemistContext``
implementation, verbatim: task-queue submission, bridge relayouts, governor
reservations, resident-store publish/attach). :class:`Session` is the v2
facade over it; the v1 :class:`AlchemistContext` remains as a deprecation
shim that subclasses the same core, so the two surfaces cannot drift.
"""

from __future__ import annotations

import contextlib
import time
import warnings
from typing import TYPE_CHECKING, Any, Dict, Iterator, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import futures as futures_mod
from repro.core import handles as handles_mod
from repro.core import params as params_codec
from repro.core.errors import LibraryError, SessionError
from repro.core.expr import (
    LazyMatrix,
    arg_shape,
    content_key,
    infer_run_shapes,
    peeked_state,
)
from repro.core.futures import AlFuture
from repro.core.handles import AlMatrix
from repro.core.layouts import GRID, ROW, LayoutSpec
from repro.core.payload import place, to_default_device
from repro.core.policy import ExecutionPolicy, PolicyLike, as_policy
from repro.core.registry import Library, LibrarySpec, load_library
from repro.core.relayout import (
    FUSED_PATHS,
    TransferRecord,
    pad_amounts,
    pad_for,
    staged_pad_path,
    timed_relayout,
    transfer_cost,
)
from repro.core.resident import ResidentEntry, ResidentStore
from repro.core.scheduler import PlacementRequest, PlacementTicket
from repro.core.transport import StagedShards, Transport, resolve_transport

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids an import cycle
    from repro.core.engine import AlchemistEngine

# Sentinel distinguishing "kwarg not passed" from an explicit None/() on the
# deprecated v1 admission kwargs (DESIGN.md §12 migration table).
_UNSET = object()


def _coerce_placement(
    placement: Optional[PlacementRequest],
    *,
    workers: Optional[int] = None,
    grid: Optional[Tuple[int, int]] = None,
    datasets: Any = _UNSET,
    queue: Any = _UNSET,
    timeout: Any = _UNSET,
    default_queue: bool,
) -> PlacementRequest:
    """Fold the v1 admission kwargs into a :class:`PlacementRequest`.

    ``workers``/``grid`` stay first-class sugar (no warning); the v1
    admission trio (``datasets``/``queue``/``timeout``) warns and maps onto
    ``affinity``/``deadline``: ``queue=False`` → ``deadline=0`` (fail fast),
    ``queue=True, timeout=t`` → ``deadline=t`` (None waits indefinitely).
    """
    legacy = [
        kw
        for kw, value in (("datasets", datasets), ("queue", queue), ("timeout", timeout))
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
        if workers is not None or grid is not None or legacy:
            raise SessionError(
                "pass either placement=PlacementRequest(...) or the legacy "
                "workers/grid/datasets/queue/timeout kwargs, not both"
            )
        return placement
    queue = default_queue if queue is _UNSET else bool(queue)
    timeout = None if timeout is _UNSET else timeout
    datasets = () if datasets is _UNSET else datasets
    deadline = (None if timeout is None else float(timeout)) if queue else 0.0
    return PlacementRequest(
        workers=workers, grid=grid, affinity=tuple(datasets), deadline=deadline
    )


class ClientCore:
    """The client-side transport: one session's bridge to the engine.

    All operations flow through the session's task queue. ``send_eager`` /
    ``run_eager`` submit a task and wait; the ``*_async`` twins submit and
    return an :class:`AlFuture`, letting transfers pipeline against compute
    within the session and letting independent sessions overlap across the
    engine. The v2 :class:`Session` and the v1 :class:`AlchemistContext` shim
    are both thin facades over this core.

    ``hbm_budget`` (bytes, optional) folds into the engine-wide governor's
    shared ceiling: sends and routine outputs are admitted against it,
    spilling least-recently/last-used matrices to a pinned host store and
    refilling them transparently on next use (DESIGN.md §7). Default:
    unlimited. Admission is declarative (DESIGN.md §12): pass
    ``placement=PlacementRequest(...)`` (workers, priority, content
    affinity, deadline, shareability); the v1 ``datasets``/``queue``/
    ``timeout`` kwargs keep working through a deprecation shim.
    """

    def __init__(
        self,
        engine: "AlchemistEngine",
        num_workers: Optional[int] = None,
        *,
        name: str = "app",
        grid: Optional[Tuple[int, int]] = None,
        client_layout: LayoutSpec = ROW,
        engine_layout: LayoutSpec = GRID,
        hbm_budget: Optional[int] = None,
        placement: Optional[PlacementRequest] = None,
        datasets: Any = _UNSET,
        queue: Any = _UNSET,
        timeout: Any = _UNSET,
        transport: Union[Transport, str, None] = None,
    ):
        self.engine = engine
        self.client_layout = client_layout
        self.engine_layout = engine_layout
        self._planner = None
        self._stopped = False
        placement = _coerce_placement(
            placement,
            workers=num_workers,
            grid=grid,
            datasets=datasets,
            queue=queue,
            timeout=timeout,
            default_queue=False,  # the v1 core failed fast by default
        )
        # The wire seam (DESIGN.md §11): every verb below reaches the engine
        # through this transport. Default comes from REPRO_TRANSPORT, so an
        # unmodified test suite can run over a localhost socket.
        self.transport = resolve_transport(transport)
        # Re-admission record (DESIGN.md §14): the kwargs a fleet recovery
        # replays through a surviving engine's queued connect path.
        self._admission = dict(name=name, hbm_budget=hbm_budget, placement=placement)
        self.session = self.transport.open_session(self, dict(self._admission))

    @classmethod
    def _over_session(cls, engine: "AlchemistEngine", session, client_layout, engine_layout):
        """Engine-side twin of a remote client (serve.wire): a core bound to
        an existing session, executing the ``_local_*`` verbs in-process.
        Never opens a transport and never owns admission — the server that
        built it releases the session on disconnect/CLOSE."""
        core = object.__new__(cls)
        core.engine = engine
        core.client_layout = client_layout
        core.engine_layout = engine_layout
        core._planner = None
        core._stopped = False
        core.transport = None
        core.session = session
        core._admission = {}
        return core

    # -- libraries -----------------------------------------------------------
    def register_library(self, name: str, spec: LibrarySpec) -> Library:
        """Load a library into this session (the paper's registerLibrary).

        ``spec`` may be a Library instance/class or an import-path string
        ``"repro.linalg.library:ElementalLib"`` — resolved only now, the
        runtime-dynamic-linking analogue. Import-path strings route through
        the transport (they are the wire-expressible form — the paper's
        "dlopen by name" request); live instances/classes are an in-process
        convenience and register directly.
        """
        self._check()
        if isinstance(spec, str):
            return self.transport.register_library(self, name, spec)
        return self._local_register_library(name, spec)

    def _local_register_library(self, name: str, spec: LibrarySpec) -> Library:
        lib = load_library(spec)
        if name != lib.name:
            # allow aliasing but keep it explicit in the session table
            lib.name = name
        self.session.libraries[name] = lib
        # Record the wire-expressible spec for the session's re-admission
        # descriptor: import-path strings verbatim, instances/classes as
        # their import path (best effort — a fleet recovery re-resolves it).
        if isinstance(spec, str):
            self.session.library_specs[name] = spec
        else:
            self.session.library_specs[name] = (
                f"{type(lib).__module__}:{type(lib).__name__}"
            )
        return lib

    def library(self, name: str) -> Library:
        self._check()
        try:
            return self.session.libraries[name]
        except KeyError:
            raise LibraryError(
                f"library {name!r} not registered in session {self.session.id}; "
                f"registered: {sorted(self.session.libraries)}"
            ) from None

    # -- matrix movement (the bridge) -----------------------------------------
    def send_async(self, array: Union[jax.Array, np.ndarray], name: str = "") -> AlFuture:
        """Pipelined RDD→Alchemist transfer: returns immediately with a
        future of the handle; the session worker stages + reshards it."""
        return self._submit_send(array, name=name, block=False)

    def send_eager(self, array: Union[jax.Array, np.ndarray], name: str = "") -> AlMatrix:
        """Ship a client-side (row-partitioned) matrix to the engine's grid
        layout and return its handle. The paper's RDD→Alchemist transfer."""
        return self._submit_send(array, name=name, block=True).result()

    def _submit_send(
        self,
        array: Union[jax.Array, np.ndarray],
        *,
        name: str,
        block: bool,
        key: Optional[Tuple] = None,
        payload: Optional[np.ndarray] = None,
    ) -> AlFuture:
        """``key``/``payload`` (internal, DESIGN.md §8): the payload's content
        key and a private host snapshot of its logical bytes, when the caller
        (the offload planner) already computed them. Validates client-side
        (fail fast), then hands the payload to the transport — which frames
        its bytes (loopback encodes/decodes in place; TCP ships them) before
        the engine-side :meth:`_local_submit_send` runs."""
        self._check()
        # Validate + capture metadata in the caller thread (fail fast, and
        # pending handles need shape/dtype before the transfer runs).
        if not isinstance(array, jax.Array):
            array = np.asarray(array)
        if array.ndim != 2:
            raise SessionError(f"send() expects a 2D matrix, got shape {tuple(array.shape)}")
        return self.transport.submit_send(
            self, array, name=name, block=block, key=key, payload=payload
        )

    def _local_submit_send(
        self,
        array: Union[jax.Array, np.ndarray],
        *,
        name: str,
        block: bool,
        key: Optional[Tuple] = None,
        payload: Optional[Union[np.ndarray, StagedShards]] = None,
    ) -> AlFuture:
        """Engine-side send: content-store attach decision, pending handle,
        governor reservation, task submission. With the engine's resident
        store enabled a content key is derived here for plain sends too, so
        every non-cyclic transfer publishes into the content index — and a
        send whose bytes another session already placed on the engine becomes
        an attach instead of a bridge crossing. ``payload`` is handed to the
        store uncopied (``ResidentStore.register``): a ``StagedShards``
        payload's slabs then belong to the store entry, not the pool."""
        sess = self.session
        store = self._content_store()
        if store is not None:
            if key is None:
                key = content_key(array)
            entry = store.lookup(key)
            if entry is not None and entry.live_handle_for(sess.id) is None and entry.usable():
                # The engine already holds these bytes (another session's
                # placement, or content migrated out of a closed one): attach
                # — an engine-internal placement, zero bridge traffic. A
                # duplicate send *within* a session keeps its classic
                # full-transfer semantics (independent handles; the planner
                # is the intra-session dedup layer).
                return self._submit_attach(key, entry, array, name=name, block=block)
        h = sess.new_pending_handle(array.shape, array.dtype, self.engine_layout, name=name)
        if store is not None:
            # Publish before the transfer runs: a concurrent session's attach
            # may pin the entry now and wait on this pending placement.
            store.register(key, h, sess, payload=payload)
        # Reserve the *physical* footprint against the HBM budget before
        # enqueueing: logical shape plus the divisibility padding the staging
        # (client) and resident (engine) layouts will append (DESIGN.md §7).
        phys = self._send_physical_shape(tuple(int(d) for d in array.shape))
        reserve_bytes = sess.memgov.reserve(
            phys[0] * phys[1] * jnp.dtype(array.dtype).itemsize
        )

        def task() -> AlMatrix:
            admitted = 0
            staged = array if isinstance(array, StagedShards) else None
            try:
                mesh = sess.mesh
                # Make room before any bytes land on the worker group: the
                # governor spills last-used resident matrices to host until
                # the incoming footprint fits the budget, and claims the room
                # so a concurrent session's admission cannot take it first.
                sess.memgov.admit(reserve_bytes)
                admitted = reserve_bytes
                if (
                    staged is not None
                    and not self.engine_layout.cyclic  # padded slabs would
                    # defeat cyclic's no-pre-pad rule; degrade below
                    and staged.matches(self.client_layout, mesh)
                ):
                    # Shard-direct send (DESIGN.md §13): the wire already
                    # decoded into per-shard slabs (pad slack zero-filled at
                    # decode) and may have overlapped the device_puts with
                    # the socket reads — assemble, never reassemble on host.
                    x = staged.device_array(self.client_layout.sharding(mesh))
                    stage_path = staged_pad_path(staged.geom.pads)
                else:
                    # A stale geometry (layout/mesh changed under the frame)
                    # degrades to the classic materialize-and-pad path.
                    x = jnp.asarray(np.asarray(array)) if staged is not None else jnp.asarray(array)
                    # Stage on the client layout first (rows over all session
                    # workers) so the recorded transfer is the genuine
                    # ROW->GRID redistribution; uneven shapes are zero-padded
                    # to the next worker-count multiple so the device_put is
                    # legal. Cyclic layouts are never pre-padded — the
                    # emulation's permutation would interleave the zero rows
                    # (see pad_amounts) — so they keep the pre-padding
                    # behaviour: even shapes work, uneven ones fail loudly at
                    # the device_put.
                    stage_path = "none"
                    if not (self.client_layout.cyclic or self.engine_layout.cyclic):
                        x, _stage_pads, stage_path = pad_for(x, self.client_layout, mesh)
                    x = jax.device_put(x, self.client_layout.sharding(mesh))
                out, rec = timed_relayout(
                    x,
                    self.engine_layout,
                    mesh,
                    src=self.client_layout,
                    direction="send",
                    cache=sess.relayout_cache,
                    block=block,
                    strip=False,  # residency keeps the put-legal physical form
                )
                rec.fused = rec.fused or stage_path in FUSED_PATHS
                sess.stats.record_transfer(rec)
                with sess.memgov.lock:  # claim -> charge atomically
                    sess.memgov.settle(admitted)
                    admitted = 0
                    h.materialize(
                        out, pads=(out.shape[0] - h.shape[0], out.shape[1] - h.shape[1])
                    )
                    sess.memgov.charge(h)
                if staged is not None:
                    # Slabs go back to the pool unless the store adopted them
                    # as its payload or a zero-copy device_put left a live
                    # array aliasing them (CPU backends).
                    staged.dispose(x, out)
                return h
            except BaseException as exc:
                if staged is not None:
                    staged.dispose()
                h.fail(exc)
                raise
            finally:
                sess.memgov.settle(admitted)
                sess.memgov.unreserve(reserve_bytes)

        return sess.tasks.submit(task, label=f"send:{name or h.id}")

    def _content_store(self) -> Optional[ResidentStore]:
        """The engine's resident store, when this session can use it: cyclic
        layouts store a physical row permutation that does not round-trip
        through the pure placement plan the attach/refill paths use."""
        store = self.engine.residents
        if not store.enabled:
            return None
        if self.client_layout.cyclic or self.engine_layout.cyclic:
            return None
        return store

    def _submit_attach(
        self,
        key: Tuple,
        entry: ResidentEntry,
        array: Union[jax.Array, np.ndarray],
        *,
        name: str,
        block: bool,
    ) -> AlFuture:
        """Produce this session's placement of an already-engine-resident
        content entry (DESIGN.md §8): an engine-internal ``device_put`` from
        the entry's host payload — no client↔engine bridge crossing, so no
        TransferRecord. Counted as ``cross_session_reuses``.

        ``array`` is the caller's own copy of the bytes: if the engine-side
        content vanishes between the attach decision and this task running
        (producer freed, orphan evicted by the retention cap), the placement
        falls back to it and is accounted as a genuine bridge send — never a
        spurious failure, never a wait on a handle that cannot materialize.

        Shared worker groups (DESIGN.md §12): when this session sits on the
        *same* worker group (same devices, same mesh geometry) as a live
        materialized placement of the content, the attach becomes a zero-byte
        **view** over that placement's device array — no ``device_put``, no
        governor charge (the source is pinned instead) — which is what makes
        the scheduler's shared-group join zero-byte engine-side.
        """
        sess = self.session
        store = self.engine.residents
        h = sess.new_pending_handle(entry.shape, entry.dtype, self.engine_layout, name=name)
        h._placement_only = True  # never a payload source while pending
        store.register(key, h, sess)
        pr, pc = pad_amounts(entry.shape, self.engine_layout, sess.mesh)
        phys = (entry.shape[0] + pr, entry.shape[1] + pc)
        reserve_bytes = sess.memgov.reserve(
            phys[0] * phys[1] * jnp.dtype(entry.dtype).itemsize
        )

        def task() -> AlMatrix:
            admitted = 0
            try:
                # Zero-byte path first: a live placement of these bytes on
                # this exact worker group can be shared in place. Checked and
                # committed under the governor lock so the source cannot be
                # spilled between the check and the pin.
                src = self._shared_view_source(entry)
                if src is not None:
                    with sess.memgov.lock:
                        if src.state == handles_mod.MATERIALIZED and src._data is not None:
                            h._host_fallback = src._host_fallback
                            h.materialize(
                                src._data,
                                pads=(
                                    src._data.shape[0] - h.shape[0],
                                    src._data.shape[1] - h.shape[1],
                                ),
                            )
                            sess.memgov.register_view(h, src)
                            sess.stats.record_shared_view()
                            sess.stats.record_cross_session_reuse()
                            store.record_attach()
                            return h
                # May block on the producing session's in-flight transfer —
                # a cross-session wait on a send task that depends on no one,
                # so it cannot deadlock the FIFOs (pending attach placements
                # are excluded as sources, see ensure_payload).
                payload = store.ensure_payload(entry)
                t0 = time.perf_counter()
                attached = payload is not None
                if not attached:
                    # The content died under us: the caller's bytes cross the
                    # bridge after all. Publish them so the content is
                    # shareable again: received slabs are adopted, an ndarray
                    # is copied (the caller may mutate it later; the entry
                    # payload must stay true to the key).
                    payload = store.register(key, h, sess, payload=array, copy=True).payload
                sess.memgov.admit(reserve_bytes)
                admitted = reserve_bytes
                # src == dst: the cached plan is a pure placement (pads only),
                # exactly the governor's refill path.
                plan, _hit = sess.relayout_cache.plan(
                    tuple(payload.shape),
                    jax.dtypes.canonicalize_dtype(payload.dtype),
                    self.engine_layout,
                    self.engine_layout,
                    sess.mesh,
                )
                out, fused = place(payload, plan)
                if fused:
                    sess.stats.record_fused_relayout()
                # Engine-side bytes this placement moved (a shared-group view
                # records none — that is the zero-byte acceptance criterion).
                sess.stats.record_placement_bytes(int(out.nbytes))
                if block:
                    out.block_until_ready()
                h._host_fallback = payload
                with sess.memgov.lock:  # claim -> charge atomically
                    sess.memgov.settle(admitted)
                    admitted = 0
                    h.materialize(
                        out, pads=(out.shape[0] - h.shape[0], out.shape[1] - h.shape[1])
                    )
                    sess.memgov.charge(h)
                if attached:
                    sess.stats.record_cross_session_reuse()
                    store.record_attach()
                else:
                    # Priced analytically: no staging relayout ran, so the
                    # plan cache's hit rate must not see this (planned=False).
                    cost = transfer_cost(
                        h.shape, h.dtype, self.client_layout, self.engine_layout, sess.mesh
                    )
                    sess.stats.record_transfer(
                        TransferRecord(
                            direction="send",
                            cost=cost,
                            seconds=time.perf_counter() - t0,
                            planned=False,
                        )
                    )
                return h
            except BaseException as exc:
                h.fail(exc)
                raise
            finally:
                sess.memgov.settle(admitted)
                sess.memgov.unreserve(reserve_bytes)

        return sess.tasks.submit(task, label=f"attach:{name or h.id}")

    def _shared_view_source(self, entry: ResidentEntry) -> Optional[AlMatrix]:
        """A live materialized placement of ``entry`` sharable in place.

        The source must belong to another session on the *same* worker group
        with the same mesh geometry and engine layout — then its device
        array is directly valid for this session's handles and the attach
        needs no engine-side bytes (DESIGN.md §12 shared worker groups).
        """
        sess = self.session
        my_ids = [d.id for d in sess.worker_devices]
        for src in entry.live_handles():
            if src.session_id == sess.id:
                continue
            if src.layout != self.engine_layout:
                continue
            src_sess = self.engine.sessions.get(src.session_id)
            if src_sess is None:
                continue
            if [d.id for d in src_sess.worker_devices] != my_ids:
                continue
            if src_sess.mesh.devices.shape != sess.mesh.devices.shape:
                continue
            if src.state == handles_mod.MATERIALIZED and src._data is not None:
                return src
        return None

    def collect_async(self, h: Union[AlMatrix, AlFuture]) -> AlFuture:
        """Future of the client-side array for ``h`` (which may itself be a
        future or a still-pending handle)."""
        return self._submit_collect(h)

    def collect(self, h: Union[AlMatrix, AlFuture]) -> jax.Array:
        """Materialize an engine-resident matrix back on the client layout.
        The only path that moves bulk data engine→client (paper §3.3)."""
        return self._submit_collect(h).result()

    def _submit_collect(self, h: Union[AlMatrix, AlFuture]) -> AlFuture:
        self._check()
        return self.transport.submit_collect(self, h)

    def _local_submit_collect(self, h: Union[AlMatrix, AlFuture]) -> AlFuture:
        sess = self.session

        def task() -> jax.Array:
            live = sess.resolve(self._resolve_handle(h))
            # A spilled matrix's bytes already sit in the host store — the
            # client side of the machine. Serving the collect from there
            # skips a pointless refill (device_put + admission that may
            # evict live working-set matrices) for data that would be pulled
            # straight back off the device. The handle stays spilled; a later
            # engine-side consumption refills as usual. Cyclic layouts store
            # permuted rows, so they take the ordinary refill path.
            host = sess.memgov.host_payload(live)
            if host is not None and not live.layout.cyclic:
                # Priced analytically (transfer_cost), not via cache.plan():
                # no relayout ran, so the plan cache and its hit/miss rate
                # must not see this transfer (planned=False below).
                cost = transfer_cost(
                    live.shape, live.dtype, live.layout, self.client_layout, sess.mesh
                )
                t0 = time.perf_counter()
                out = to_default_device(host, live.shape[0], live.shape[1])
                out.block_until_ready()
                rec = TransferRecord(
                    direction="receive",
                    cost=cost,
                    seconds=time.perf_counter() - t0,
                    planned=False,
                )
                sess.stats.record_transfer(rec)
                return out
            out, rec = timed_relayout(
                live.data(),
                self.client_layout,
                sess.mesh,
                src=live.layout,
                direction="receive",
                cache=sess.relayout_cache,
                block=True,  # collect crosses the bridge: always materialize
            )
            sess.stats.record_transfer(rec)
            return out

        return sess.tasks.submit(task, label="collect")

    def free_async(self, h: Union[AlMatrix, AlFuture]) -> AlFuture:
        self._check()
        return self.transport.free(self, h)

    def _local_free_async(self, h: Union[AlMatrix, AlFuture]) -> AlFuture:
        sess = self.session
        return sess.tasks.submit(
            lambda: sess.free_handle(self._resolve_handle(h)), label="free"
        )

    def free(self, h: Union[AlMatrix, AlFuture]) -> None:
        # Routed through the queue so frees stay FIFO-ordered behind any
        # already-submitted task that still consumes the handle.
        self.free_async(h).result()

    def _send_physical_shape(self, shape: Tuple[int, int]) -> Tuple[int, int]:
        """Physical shape a sent matrix will occupy once resident: the
        logical shape padded first for the client-layout staging put, then
        for the engine-layout relayout — the exact sequence the send task
        performs (pad_for + timed_relayout(strip=False)). Keep the two in
        lockstep: memgov reservations are priced off this prediction, and the
        eventual charge uses the materialized array's real shape."""
        if self.client_layout.cyclic or self.engine_layout.cyclic:
            return shape  # cyclic layouts are never pre-padded (see the task)
        mesh = self.session.mesh
        pr, pc = pad_amounts(shape, self.client_layout, mesh)
        phys = (shape[0] + pr, shape[1] + pc)
        pr, pc = pad_amounts(phys, self.engine_layout, mesh)
        return (phys[0] + pr, phys[1] + pc)

    def _resolve_handle(self, h: Union[AlMatrix, AlFuture]) -> AlMatrix:
        resolved = futures_mod.resolve(h)
        if isinstance(resolved, params_codec.HandleRef):
            # Wire decay: over a real transport an AlMatrix crosses as a
            # HandleRef; resolve it against the session table here, at task
            # time, so unknown/freed/foreign ids fail with the same
            # HandleError surface the in-process path has (resolve is
            # duck-typed over .id/.session_id).
            resolved = self.session.resolve(resolved)
        if not isinstance(resolved, AlMatrix):
            raise SessionError(
                f"expected an AlMatrix (or a future of one), got {type(resolved).__name__}"
            )
        return resolved

    # -- routine invocation ----------------------------------------------------
    def run_async(
        self,
        library: str,
        routine: str,
        *args: Any,
        _out_shapes: Optional[Sequence] = None,
        _out_dtype: Any = None,
        **params: Any,
    ) -> AlFuture:
        """Pipelined routine invocation: enqueue it and return a future of
        its (wrapped) outputs. Arguments may be AlMatrix handles, futures of
        handles from earlier async calls, or plain scalars; the compute is
        async-dispatched, so the worker immediately proceeds to the next task
        while XLA executes.

        ``_out_shapes`` / ``_out_dtype`` (internal) let a caller that already
        ran shape inference — the offload planner, whose operands are still
        futures here — pass the routine's output shapes and element type so
        the memory governor can reserve their bytes up front."""
        return self._submit_run(
            library,
            routine,
            args,
            params,
            block=False,
            out_shapes=_out_shapes,
            out_dtype=_out_dtype,
        )

    def run_eager(self, library: str, routine: str, *args: Any, **params: Any) -> Any:
        """Invoke ``library.routine`` on the engine (the paper's ``ac.run``).

        Positional args may be AlMatrix handles (resolved engine-side) or
        plain scalars; keyword params must be scalars/small lists and travel
        through the Parameters codec, exactly like the paper's driver-to-
        driver metadata channel.
        """
        return self._submit_run(library, routine, args, params, block=True).result()

    def _submit_run(
        self,
        library: str,
        routine: str,
        args: Tuple[Any, ...],
        params: Dict[str, Any],
        *,
        block: bool,
        out_shapes: Optional[Sequence] = None,
        out_dtype: Any = None,
    ) -> AlFuture:
        self._check()
        # Fail-fast validation stays caller-side in every transport: library
        # and routine existence (the session's library table is shared with
        # the engine-side core), then dispatch through the wire seam.
        lib = self.library(library)
        lib.routine(routine)  # unknown-routine errors fail fast, caller-side
        return self.transport.submit_run(
            self,
            library,
            routine,
            args,
            params,
            block=block,
            out_shapes=out_shapes,
            out_dtype=out_dtype,
        )

    def _local_submit_run(
        self,
        library: str,
        routine: str,
        args: Tuple[Any, ...],
        params: Dict[str, Any],
        *,
        block: bool,
        out_shapes: Optional[Sequence] = None,
        out_dtype: Any = None,
    ) -> AlFuture:
        lib = self.library(library)
        r = lib.routine(routine)
        sess = self.session
        label = f"{library}.{routine}"
        # Caller-side shape inference (per-routine rules, DESIGN.md §7): a
        # dimension mismatch raises ShapeError here, at the call site, and a
        # successful inference prices the routine's matrix outputs so the
        # governor can reserve their bytes before the task is enqueued. The
        # planner passes its own inference in (its operands are futures whose
        # shapes this layer cannot see).
        if out_shapes is None:
            out_shapes = infer_run_shapes(
                routine, [arg_shape(a) for a in args], params
            )
        reserve_bytes = 0
        if out_shapes:
            if out_dtype is None:
                # Best-known operand dtype: a handle directly, or one behind
                # an already-resolved future (the planner also passes an
                # explicit hint, since its operands may still be in flight).
                for a in args:
                    if isinstance(a, AlFuture) and a.done() and a.exception() is None:
                        a = a.result()
                    if isinstance(a, AlMatrix):
                        out_dtype = a.dtype
                        break
            itemsize = jnp.dtype(out_dtype).itemsize if out_dtype is not None else 4
            est = sum(
                int(np.prod(s)) for s in out_shapes if s is not None and len(s) == 2
            )
            reserve_bytes = sess.memgov.reserve(est * itemsize)

        def task() -> Any:
            # Resolve futures from earlier tasks (same-session ones are
            # guaranteed done: the FIFO ran their producers first).
            rargs = tuple(futures_mod.resolve(a) for a in args)
            rparams = {k: futures_mod.resolve(v) for k, v in params.items()}

            # Drive every scalar through the wire codec: this is the
            # driver->driver parameter frame of §2.1 (and catches
            # unserializable arguments at the API boundary, as the real
            # system would).
            frame = params_codec.pack(
                {f"__pos_{i}": a for i, a in enumerate(rargs)} | rparams
            )
            decoded = params_codec.unpack(frame)

            def handle_of(v: Any) -> Any:
                return sess.get_handle(v.id) if isinstance(v, params_codec.HandleRef) else v

            pos = [handle_of(decoded[f"__pos_{i}"]) for i in range(len(rargs))]
            kw = {
                k: handle_of(v)
                for k, v in decoded.items()
                if not k.startswith("__pos_")
            }
            inputs = [v for v in (*pos, *kw.values()) if isinstance(v, AlMatrix)]

            admitted = 0
            try:
                # Inputs stay pinned (unspillable) while the routine runs:
                # admission for the outputs must not evict an operand, and a
                # spilled operand refills exactly once. Reading .data()
                # inside the pin is what triggers those refills.
                with sess.memgov.pinned(inputs):
                    call_args = [
                        v.data() if isinstance(v, AlMatrix) else v for v in pos
                    ]
                    call_kwargs = {
                        k: (v.data() if isinstance(v, AlMatrix) else v)
                        for k, v in kw.items()
                    }
                    # Admit the outputs only after every operand is resolved:
                    # a .data() above may have refilled a spilled input, and
                    # room made earlier would have been eaten again. The
                    # claim holds the room against concurrent sessions until
                    # the outputs' charges land.
                    sess.memgov.admit(reserve_bytes)
                    admitted = reserve_bytes

                    if "mesh" in r.signature().parameters:
                        call_kwargs["mesh"] = sess.mesh

                    t0 = time.perf_counter()
                    with sess.mesh, jax.profiler.TraceAnnotation("al.routine", routine=label):
                        result = r.fn(*call_args, **call_kwargs)
                    if block:
                        result = jax.block_until_ready(result)
                    sess.stats.record_compute(time.perf_counter() - t0)

                    with sess.memgov.lock:  # claim -> charges atomically
                        sess.memgov.settle(admitted)
                        admitted = 0
                        return self._wrap_outputs(result, label)
            finally:
                sess.memgov.settle(admitted)
                sess.memgov.unreserve(reserve_bytes)

        return sess.tasks.submit(task, label=f"run:{label}")

    def _wrap_outputs(self, result: Any, label: str) -> Any:
        """Array outputs become engine-resident handles; scalars/vectors are
        non-distributed outputs and return to the driver directly."""
        if isinstance(result, (tuple, list)):
            wrapped = tuple(self._wrap_outputs(r, label) for r in result)
            return type(result)(wrapped) if isinstance(result, list) else wrapped
        if isinstance(result, jax.Array) and result.ndim == 2:
            return self.session.new_handle(result, self.engine_layout, name=label)
        if isinstance(result, jax.Array) and result.ndim <= 1:
            return np.asarray(result)
        return result

    # -- lazy offload planner -----------------------------------------------
    @property
    def planner(self):
        """This session's :class:`~repro.core.planner.OffloadPlanner` (lazily
        created, one per client so its resident-matrix cache and elision
        counters are session-scoped, DESIGN.md §6)::

            pl = ac.planner
            la = pl.send(a)
            u, s, v = pl.run("elemental", "truncated_svd", la, n_outputs=3, k=8)
            proj = pl.run("elemental", "gemm", la, u)   # u never leaves the engine
            P = pl.collect(proj)                        # the one bridge crossing
        """
        self._check()
        if self._planner is None:
            from repro.core.planner import OffloadPlanner

            self._planner = OffloadPlanner(self)
        return self._planner

    # -- lifecycle ---------------------------------------------------------------
    def wait(self, timeout: Optional[float] = None) -> None:
        """Barrier: block until every task this session has queued so far
        (sends, runs, collects, frees) has executed."""
        self._check()
        self.transport.barrier(self, timeout)

    @property
    def stats(self):
        return self.session.stats

    @property
    def mesh(self) -> Mesh:
        return self.session.mesh

    def rebind(
        self,
        engine: "AlchemistEngine",
        *,
        transport: Union[Transport, str, None] = None,
        placement: Optional[PlacementRequest] = None,
    ) -> "Session":
        """Fail this core over to another engine (fleet recovery,
        DESIGN.md §14).

        Re-admits through ``engine``'s queued connect path using the
        original admission kwargs (optionally overriding the placement),
        swaps the transport and engine-side session **in place** — live
        :class:`AlArray` handles keep working because they reference this
        core, never the dead session — re-registers the old session's
        wire-expressible libraries, and drops the planner's lowering memos
        so the next materialization replays exactly the DAG suffix whose
        engine-side outputs were lost. Returns the new engine-side session.
        """
        specs = dict(getattr(self.session, "library_specs", None) or {})
        kwargs = dict(self._admission)
        if placement is not None:
            kwargs["placement"] = placement
        self.engine = engine
        self.transport = resolve_transport(transport)
        self.session = self.transport.open_session(self, kwargs)
        for lname, spec in specs.items():
            self.transport.register_library(self, lname, spec)
        if self._planner is not None:
            self._planner.reset()
        self._stopped = False
        return self.session

    def stop(self) -> None:
        """Disconnect and release the worker group (paper's ``ac.stop()``).

        Queued tasks are drained first (their futures resolve), then the
        worker-group devices return to the engine pool in canonical order —
        waking any ``connect()`` queued for admission.
        """
        if not self._stopped:
            self.transport.close_session(self)
            self._stopped = True

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def _check(self) -> None:
        if self._stopped:
            raise SessionError(f"{type(self).__name__} has been stopped")


class AlArray(LazyMatrix):
    """The uniform v2 matrix handle: a deferred engine-resident array.

    Unifies the three v1 handle types (DESIGN.md §9): like a ``LazyMatrix``
    it is an expression node (ops chain without executing), like an
    ``AlFuture`` it can be waited on (``.result(timeout)`` / ``await``), and
    like an ``AlMatrix`` it names engine-resident data (``.state``,
    ``.free()``, ``.materialize()``). Whether building one *executes*
    anything is the owning session's :class:`ExecutionPolicy` — the handle
    API is identical under all three.

    - ``.data()`` / ``.result()`` / ``await`` — force the DAG through the
      planner and return the client-side value (the one bridge crossing).
    - ``.materialize()`` — force execution but keep matrix data
      engine-resident; returns the raw engine-side value.
    - ``.state`` — where the value physically is: ``deferred`` / ``pending``
      / ``materialized`` / ``spilled`` / ``failed`` / ``freed``.
    - ``.free()`` — release engine-side storage, if any was ever produced.
    """

    def __init__(self, expr, planner, session: "Session"):
        super().__init__(expr, planner)
        self._session = session

    # -- chaining (policy-aware: the session decides when this executes) -----
    def __matmul__(self, other: Any) -> "AlArray":
        lib, routine = self.planner.matmul_routine
        return self._session.run(lib, routine, self, other)

    def __rmatmul__(self, other: Any) -> "AlArray":
        lib, routine = self.planner.matmul_routine
        return self._session.run(lib, routine, other, self)

    # -- forcing -------------------------------------------------------------
    def data(self) -> Any:
        """Force execution through the planner and return the client-side
        value: an array for matrix nodes, the scalar/vector itself for
        driver-side routine outputs."""
        return self.planner.collect(self)

    def result(self, timeout: Optional[float] = None) -> Any:
        """AlFuture-compatible spelling of :meth:`data`. ``timeout`` bounds
        the wait for the engine-side execution (raises
        :class:`~repro.core.errors.TaskError` like a future would)."""
        if timeout is not None:
            futures_mod.resolve(self.planner.lower(self), timeout)
        return self.data()

    def __await__(self):
        """``await arr`` forces off the event loop's thread: the blocking
        planner collect runs in the default executor, so concurrent awaits
        on independent DAGs pipeline like the v1 ``*_async`` surface."""
        import asyncio

        loop = asyncio.get_running_loop()
        return loop.run_in_executor(None, self.data).__await__()

    # -- residency -----------------------------------------------------------
    @property
    def state(self) -> str:
        """Physical placement of this node's value (never forces execution)."""
        return peeked_state(self.planner.peek(self))

    def free(self) -> None:
        """Release the engine-side storage behind this node, if its lowering
        ever produced any. A deferred node has no resources; freeing it is a
        no-op (and a later force transparently re-executes, the documented
        planner semantics)."""
        val = self.planner.peek(self)
        if isinstance(val, AlFuture):
            if val.exception() is not None:  # blocks until the task settled
                return
            val = val.result()
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, AlMatrix) and v.is_live:
                self._session.free(v)

    def __repr__(self) -> str:
        return f"AlArray({self.expr!r}, state={self.state})"


class Session(ClientCore):
    """The v2 client session: uniform :class:`AlArray` handles, pluggable
    execution policy, admission-aware placement. Built by :func:`connect`.

    Every verb builds expression nodes; the session's policy decides when
    they execute. ``close()`` (or the context manager) drains the queue and
    returns the worker group — waking any queued ``connect()``.
    """

    def __init__(
        self,
        engine: "AlchemistEngine",
        *,
        name: str = "app",
        workers: Optional[int] = None,
        grid: Optional[Tuple[int, int]] = None,
        hbm_budget: Optional[int] = None,
        policy: PolicyLike = None,
        placement: Optional[PlacementRequest] = None,
        datasets: Any = _UNSET,
        queue: Any = _UNSET,
        timeout: Any = _UNSET,
        client_layout: LayoutSpec = ROW,
        engine_layout: LayoutSpec = GRID,
        transport: Union[Transport, str, None] = None,
    ):
        self._policy = as_policy(policy)
        # Coerce here (not in the core) so the v2 default applies: a Session
        # queues indefinitely unless the request says otherwise.
        placement = _coerce_placement(
            placement,
            workers=workers,
            grid=grid,
            datasets=datasets,
            queue=queue,
            timeout=timeout,
            default_queue=True,
        )
        super().__init__(
            engine,
            name=name,
            client_layout=client_layout,
            engine_layout=engine_layout,
            hbm_budget=hbm_budget,
            placement=placement,
            transport=transport,
        )

    # -- placement ------------------------------------------------------------
    @property
    def placement(self) -> PlacementTicket:
        """The resolved placement ticket (DESIGN.md §12): devices, shared or
        private, queue wait in ns, and the scheduler's scoring breakdown."""
        return self.session.placement

    # -- policy ---------------------------------------------------------------
    @property
    def execution_policy(self) -> ExecutionPolicy:
        return self._policy

    @contextlib.contextmanager
    def policy(self, policy: PolicyLike) -> Iterator["Session"]:
        """Scope an execution policy::

            with session.policy("eager"):
                b = session.send(B)     # executes (and blocks) immediately
        """
        prev = self._policy
        self._policy = as_policy(policy)
        try:
            yield self
        finally:
            self._policy = prev

    def _adopt(self, lazy: LazyMatrix) -> AlArray:
        arr = AlArray(lazy.expr, self.planner, self)
        self._policy.apply(self.planner, arr)
        return arr

    # -- the v2 verbs ---------------------------------------------------------
    def send(self, array: Any, name: str = "") -> AlArray:
        """Declare a host→engine transfer; returns an :class:`AlArray`.
        Equal payloads dedup (session-local and engine-wide); when the
        transfer happens is the execution policy's call."""
        self._check()
        return self._adopt(self.planner.send(array, name=name))

    def run(
        self,
        library: str,
        routine: str,
        *args: Any,
        n_outputs: int = 1,
        **params: Any,
    ):
        """Declare ``library.routine`` over AlArrays / host arrays / scalars;
        returns an :class:`AlArray` (or a tuple of them for
        ``n_outputs > 1``). Chains validate shapes at the call site."""
        self._check()
        out = self.planner.run(library, routine, *args, n_outputs=n_outputs, **params)
        if isinstance(out, tuple):
            return tuple(self._adopt(o) for o in out)
        return self._adopt(out)

    # -- uniform collect/free over v2 handles ---------------------------------
    def collect(self, h: Union[AlArray, AlMatrix, AlFuture]) -> Any:
        if isinstance(h, LazyMatrix):
            return self.planner.collect(h)
        return super().collect(h)

    def free(self, h: Union[AlArray, AlMatrix, AlFuture]) -> None:
        if isinstance(h, AlArray):
            h.free()
            return
        super().free(h)

    # -- lifecycle ------------------------------------------------------------
    def close(self) -> None:
        """v2 spelling of :meth:`ClientCore.stop`."""
        self.stop()


def connect(
    engine: "AlchemistEngine",
    *,
    name: str = "app",
    workers: Optional[int] = None,
    grid: Optional[Tuple[int, int]] = None,
    hbm_budget: Optional[int] = None,
    policy: PolicyLike = None,
    placement: Optional[PlacementRequest] = None,
    datasets: Any = _UNSET,
    queue: Any = _UNSET,
    timeout: Any = _UNSET,
    client_layout: LayoutSpec = ROW,
    engine_layout: LayoutSpec = GRID,
    transport: Union[Transport, str, None] = None,
) -> Session:
    """Connect an application to an :class:`AlchemistEngine` (DESIGN.md §9).

    - ``placement`` is the declarative admission request (DESIGN.md §12): a
      :class:`~repro.core.scheduler.PlacementRequest` naming the group size,
      priority, content affinity, admission deadline, and whether a shared
      worker group may serve it. The resolved ticket is exposed as
      ``session.placement``.
    - ``workers`` / ``grid`` remain sugar for a request with just a size
      (default: every currently free device, queueing indefinitely).
    - ``policy`` selects execution: ``"planned"`` (default), ``"pipelined"``,
      ``"eager"`` — an :class:`ExecutionPolicy` name, class, or instance.
    - ``datasets`` / ``queue`` / ``timeout`` are the deprecated v1 admission
      kwargs; they keep working through a shim that folds them into the
      request (``affinity`` / ``deadline`` — see the §12 migration table).
    - ``hbm_budget`` folds into the engine-wide governor ceiling (§7).
    - ``transport`` selects the wire (DESIGN.md §11): ``"loopback"``
      (default; in-process, frames still encoded/decoded) or ``"tcp"``
      (a localhost socket to a threaded :class:`~repro.serve.wire.
      EngineServer` wrapping the engine). ``REPRO_TRANSPORT`` sets the
      process-wide default.
    """
    legacy: Dict[str, Any] = {}
    if datasets is not _UNSET:
        legacy["datasets"] = datasets
    if queue is not _UNSET:
        legacy["queue"] = queue
    if timeout is not _UNSET:
        legacy["timeout"] = timeout
    return Session(
        engine,
        name=name,
        workers=workers,
        grid=grid,
        hbm_budget=hbm_budget,
        policy=policy,
        placement=placement,
        client_layout=client_layout,
        engine_layout=engine_layout,
        transport=transport,
        **legacy,
    )


class AlchemistContext(ClientCore):
    """Deprecated v1 ACI — a thin shim over the v2 client core.

    The paper-era surface (``send``/``run``/``collect``/``*_async`` +
    ``ac.planner``) delegates to the same :class:`ClientCore` transport the
    v2 :class:`Session` uses, so behaviour, stats, and error surfaces are
    identical; only the entry point is deprecated. Migrate with the
    DESIGN.md §9 table: ``repro.connect(engine, workers=n)`` and uniform
    :class:`AlArray` handles replace the per-call choice between eager,
    async, and planner APIs.
    """

    def __init__(
        self,
        engine: "AlchemistEngine",
        num_workers: Optional[int] = None,
        *,
        name: str = "app",
        grid: Optional[Tuple[int, int]] = None,
        client_layout: LayoutSpec = ROW,
        engine_layout: LayoutSpec = GRID,
        hbm_budget: Optional[int] = None,
        transport: Union[Transport, str, None] = None,
    ):
        warnings.warn(
            "AlchemistContext is deprecated; connect with "
            "`session = repro.connect(engine, workers=...)` and use AlArray "
            "handles with an ExecutionPolicy (DESIGN.md §9 has the "
            "call-for-call migration table)",
            DeprecationWarning,
            stacklevel=2,
        )
        super().__init__(
            engine,
            num_workers,
            name=name,
            grid=grid,
            client_layout=client_layout,
            engine_layout=engine_layout,
            hbm_budget=hbm_budget,
            transport=transport,
        )

    # The v1 spellings: eager send/run under the classic names.
    send = ClientCore.send_eager
    run = ClientCore.run_eager
