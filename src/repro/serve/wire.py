"""EngineServer + TcpTransport — the engine behind a real socket.

DESIGN.md §11/§13. The paper's deployment is two processes bridged by a
network: Spark's driver speaks to the Alchemist driver over a socket, matrix
payloads cross between worker sets, and a dropped connection must return the
client's worker group to the pool. This module is that server for the
reproduction:

- :class:`EngineServer` — a threaded TCP server wrapping one
  :class:`~repro.core.engine.AlchemistEngine`. Each accepted connection binds
  at most one session (CONNECT allocates it; HELLO with a session token
  re-binds an existing one after a drop). Requests are length-prefixed ALWF
  control frames (:mod:`repro.core.transport`) executed against an
  engine-side :class:`~repro.core.client.ClientCore` twin; replies are
  OK/ERR/ARRAY frames. A disconnect releases the bound session — its worker
  group returns to the pool — unless ``linger > 0`` grants a reconnect
  window for the token to re-bind within.
- :class:`TcpTransport` — the client half of the seam: the same five verbs
  as loopback, spoken over a localhost socket. Submission verbs return after
  the server *enqueues* (an integer ticket names the engine-side future);
  collect results are pulled with FETCH, which streams the array back in
  per-shard slabs.

**The v2 data plane (PR 9).** Wire version 2 makes the socket a streaming,
pipelined path instead of stop-and-wait:

- *Multi-in-flight RPC*: every request carries a client-minted ``__rid``;
  replies echo it. A reader thread on the client demultiplexes, so sends,
  runs, FETCHes, and barriers interleave on one socket — the server runs
  blocking verbs (FETCH result waits, BARRIER drains) on worker threads with
  a per-connection write lock serializing reply frames.
- *Shard-direct receive*: a SEND whose ARRAY frame declares shard-aligned
  chunking (``__shards``/``__srows``) decodes each chunk straight into a
  per-shard staging slab from the governor's pool and can overlap per-shard
  ``device_put`` with the remaining socket reads — no full-array reassembly
  buffer. Frames without the geometry take the classic reassembly path.
- *Streamed FETCH*: row-slab shards of the collected array are pulled off
  the device one slab at a time (next slab's ``device_get`` overlaps the
  current slab's socket write) and coalesced into vectored ``sendmsg``
  writes.
- *Version gate*: HELLO/CONNECT carry ``__version``; a mismatched client
  gets a typed ERR naming both versions, never garbage frames.

Loopback-parity deployment: the server thread lives in the engine's process
(``ensure_server``), so handles and futures the RPCs name can be resolved to
the live in-process objects (``session_object``/``take_future``) while every
control frame and payload byte genuinely crosses the socket. The bridge-byte
accounting (``SessionStats``) runs engine-side in both transports, which is
what makes the loopback and TCP counters comparable — the wire benchmark's
parity check and CI's ``REPRO_TRANSPORT=tcp`` tier-1 run both lean on this.
A fully remote client would add a client-side handle cache; the protocol
already carries everything it needs (handles cross as HandleRefs, futures as
tickets, arrays as framed bytes).
"""

from __future__ import annotations

import itertools
import json
import socket
import struct
import threading
import uuid
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import transport as wire
from repro.core.errors import AlchemistError, ParameterError, SessionError, TaskError
from repro.core.futures import AlFuture
from repro.core.layouts import by_name as layout_by_name
from repro.core.params import HandleRef
from repro.core.transport import Transport

_SERVERS: Dict[int, "EngineServer"] = {}
_SERVERS_LOCK = threading.Lock()


def ensure_server(engine, **kwargs) -> "EngineServer":
    """The engine's singleton wire server, started on first use."""
    with _SERVERS_LOCK:
        srv = _SERVERS.get(id(engine))
        if srv is None or srv.closed:
            srv = EngineServer(engine, **kwargs)
            _SERVERS[id(engine)] = srv
        return srv


def server_for(engine) -> Optional["EngineServer"]:
    """The engine's live wire server, if one was ever started (stats hook)."""
    with _SERVERS_LOCK:
        srv = _SERVERS.get(id(engine))
        return None if srv is None or srv.closed else srv


class _Bound:
    """One session's server-side state: the engine core twin, the ticket
    table naming its in-flight futures, and the reconnect bookkeeping."""

    def __init__(self, token: str, session, core):
        self.token = token
        self.session = session
        self.core = core
        self.futures: Dict[int, AlFuture] = {}
        self._tickets = itertools.count(1)
        self.lock = threading.Lock()
        self.released = False
        self.linger_timer: Optional[threading.Timer] = None

    def ticket(self, fut: AlFuture) -> int:
        with self.lock:
            t = next(self._tickets)
            self.futures[t] = fut
        return t

    def future(self, t: int) -> AlFuture:
        with self.lock:
            try:
                return self.futures[t]
            except KeyError:
                raise SessionError(f"unknown ticket {t} for session {self.session.id}") from None


class _ConnState:
    """Per-connection v2 state: the reply write lock (worker threads and the
    connection loop interleave OK/ERR/ARRAY frames on one socket) and the
    in-flight request depth."""

    def __init__(self, sock: Optional[socket.socket] = None):
        self.wlock = threading.RLock()
        self.inflight = 0
        self.max_inflight = 0
        self._lock = threading.Lock()
        self.sock = sock

    def shutdown(self) -> None:
        """Tear the socket down under the peer: blocked ``recv``/``send``
        calls in the connection loop and worker threads return immediately
        instead of serving a stopped engine."""
        if self.sock is None:
            return
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass

    def enter(self) -> int:
        with self._lock:
            self.inflight += 1
            self.max_inflight = max(self.max_inflight, self.inflight)
            return self.inflight

    def exit(self) -> None:
        with self._lock:
            self.inflight -= 1


class EngineServer:
    """Threaded TCP server wrapping an AlchemistEngine (DESIGN.md §11)."""

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 0, linger: float = 0.0):
        self.engine = engine
        self.linger = linger
        self.closed = False
        self._sock = socket.create_server((host, port))
        self.address: Tuple[str, int] = self._sock.getsockname()[:2]
        self._lock = threading.Lock()
        self._stop_lock = threading.Lock()
        self._stopped = False
        self._bound: Dict[str, _Bound] = {}
        self._conns: List[_ConnState] = []
        self.stats = {
            "connections": 0,
            "disconnect_releases": 0,  # sessions torn down by a dropped socket
            "reconnects": 0,  # HELLO re-binds within the linger window
            "frames": 0,
            "bytes_in": 0,
            "bytes_out": 0,
            # -- v2 data plane (DESIGN.md §13) --------------------------------
            "vectored_writes": 0,  # sendmsg syscall batches on replies
            "shard_direct_receives": 0,  # SENDs decoded straight into shard slabs
            "reassembly_receives": 0,  # SENDs through the one-buffer fallback
            "streamed_fetches": 0,  # FETCHes streamed slab-by-slab off device
            "gathered_fetches": 0,  # FETCHes through the full-gather fallback
            "overlap_ns": 0,  # Σ device_put time inside the socket window
            "put_ns": 0,  # Σ device_put time on shard-direct receives
            "max_inflight": 0,  # deepest per-connection request pipeline seen
            "version_rejects": 0,  # HELLO/CONNECTs refused on __version
        }
        self._accept = threading.Thread(
            target=self._accept_loop, name=f"wire-{self.address[1]}", daemon=True
        )
        self._accept.start()

    # -- in-process parity lookups (see module docstring) --------------------
    def session_object(self, token: str):
        return self._require(token).session

    def take_future(self, token: str, ticket: int) -> AlFuture:
        return self._require(token).future(ticket)

    def register_future(self, token: str, fut: AlFuture) -> int:
        """Admit an engine-side future the server did not itself mint into
        the session's ticket table (derived futures: `.then` projections the
        planner builds over RUN outputs). In-process parity only — a fully
        remote client would await the projection and reference the handle."""
        return self._require(token).ticket(fut)

    def _require(self, token: str) -> _Bound:
        with self._lock:
            try:
                return self._bound[token]
            except KeyError:
                raise SessionError(f"unknown or expired session token {token!r}") from None

    def has_session(self, token: str) -> bool:
        with self._lock:
            return token in self._bound

    def inflight_depth(self) -> int:
        """Requests currently executing across all live connections."""
        with self._lock:
            return sum(c.inflight for c in self._conns)

    # -- lifecycle -----------------------------------------------------------
    def stop(self) -> None:
        """Stop accepting, release every still-bound session, and shut down
        live per-connection sockets so mid-FETCH worker threads unblock.

        Safe to call from a supervisor thread at any time, including while
        connection loops and data-plane workers are active; a second (or
        concurrent) stop is a no-op. The stop flag is claimed under its own
        lock so a re-entrant call never deadlocks against ``_release`` or a
        connection teardown holding ``self._lock``.
        """
        with self._stop_lock:
            if self._stopped:
                return
            self._stopped = True
        self.closed = True
        # shutdown() before close(): a thread parked in accept() holds the
        # listening socket's open file description, so close() alone leaves
        # the port accepting connections until that thread wakes. shutdown
        # forces the blocked accept to return so the listener really dies.
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        with self._lock:
            bound = list(self._bound.values())
            self._bound.clear()
            conns = list(self._conns)
        for b in bound:
            self._release(b, why="server stop")
        for c in conns:
            c.shutdown()

    def close(self) -> None:
        """Alias for :meth:`stop` (historical name)."""
        self.stop()

    def _release(self, b: _Bound, why: str) -> None:
        with self._lock:
            if b.released:
                return
            b.released = True
            self._bound.pop(b.token, None)
            if b.linger_timer is not None:
                b.linger_timer.cancel()
        # engine.release drains the session queue and returns the worker
        # group to the pool in canonical order, waking queued connects.
        self.engine.release(b.session)

    # -- server loop ---------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self.closed:
            try:
                conn, _addr = self._sock.accept()
            except OSError:
                return  # listener closed
            self.stats["connections"] += 1
            threading.Thread(
                target=self._serve_connection,
                args=(conn,),
                daemon=True,
                name=f"wire-conn-{self.stats['connections']}",
            ).start()

    def _serve_connection(self, conn: socket.socket) -> None:
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        cstate = _ConnState(conn)
        with self._lock:
            self._conns.append(cstate)
        bound: Optional[_Bound] = None
        explicit_close = False
        try:
            while True:
                try:
                    ftype, req, nread = wire.recv_frame(conn)
                except ConnectionError:
                    break  # peer vanished: disconnect semantics below
                self.stats["frames"] += 1
                self.stats["bytes_in"] += nread
                rid = req.pop("__rid", None)
                try:
                    bound, closed = self._dispatch(conn, cstate, ftype, req, bound, rid)
                    if closed:
                        explicit_close = True
                        break
                except AlchemistError as exc:
                    self._reply(conn, cstate, wire.T_ERR, wire.error_payload(exc), rid)
                except Exception as exc:  # noqa: BLE001 — map, never crash the loop
                    self._reply(conn, cstate, wire.T_ERR, wire.error_payload(exc), rid)
        except (ConnectionError, OSError):
            pass  # reply write failed: same as a disconnect
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._lock:
                self.stats["max_inflight"] = max(
                    self.stats["max_inflight"], cstate.max_inflight
                )
                if cstate in self._conns:
                    self._conns.remove(cstate)
            if bound is not None and not explicit_close and not bound.released:
                if self.linger > 0:
                    # Reconnect window: keep the session bound; release only
                    # if no HELLO re-binds the token in time.
                    self._schedule_linger(bound)
                else:
                    self.stats["disconnect_releases"] += 1
                    self._release(bound, why="disconnect")

    def _schedule_linger(self, b: _Bound) -> None:
        def expire() -> None:
            with self._lock:
                if b.released or b.token not in self._bound:
                    return
            self.stats["disconnect_releases"] += 1
            self._release(b, why="linger expired")

        t = threading.Timer(self.linger, expire)
        t.daemon = True
        b.linger_timer = t
        t.start()

    def _reply(
        self,
        conn: socket.socket,
        cstate: _ConnState,
        ftype: int,
        payload: Dict[str, Any],
        rid: Optional[int],
    ) -> None:
        if rid is not None:
            payload = {**payload, "__rid": int(rid)}
        with cstate.wlock:
            n = wire.send_frame(conn, ftype, payload)
        self.stats["bytes_out"] += n

    def _spawn(self, cstate: _ConnState, fn, label: str) -> None:
        """Run a blocking verb off the connection loop so later requests on
        the same socket proceed (multi-in-flight). The per-connection write
        lock keeps its eventual reply frame atomic."""
        cstate.enter()
        self.stats["max_inflight"] = max(self.stats["max_inflight"], cstate.max_inflight)

        def run() -> None:
            try:
                fn()
            finally:
                cstate.exit()

        threading.Thread(target=run, daemon=True, name=label).start()

    # -- verb dispatch -------------------------------------------------------
    def _check_version(self, req: Dict[str, Any]) -> None:
        v = int(req.get("__version") or 1)
        if v != wire.WIRE_VERSION:
            self.stats["version_rejects"] += 1
            raise SessionError(
                f"wire protocol version mismatch: client speaks v{v}, "
                f"server speaks v{wire.WIRE_VERSION} — upgrade the client "
                "(frame formats are incompatible across versions)"
            )

    def _dispatch(
        self,
        conn: socket.socket,
        cstate: _ConnState,
        ftype: int,
        req: Dict[str, Any],
        bound: Optional[_Bound],
        rid: Optional[int],
    ) -> Tuple[Optional[_Bound], bool]:
        if ftype == wire.T_HELLO:
            self._check_version(req)
            token = req.get("__token")
            if token:
                bound = self._require(str(token))
                if bound.linger_timer is not None:
                    bound.linger_timer.cancel()
                    bound.linger_timer = None
                self.stats["reconnects"] += 1
                self._reply(
                    conn, cstate, wire.T_OK,
                    {"__sid": bound.session.id, "__version": wire.WIRE_VERSION}, rid,
                )
            else:
                self._reply(conn, cstate, wire.T_OK, {"__version": wire.WIRE_VERSION}, rid)
            return bound, False

        if ftype == wire.T_CONNECT:
            self._check_version(req)
            if bound is not None:
                raise SessionError("connection already has a bound session")
            bound = self._connect(req)
            self._reply(
                conn, cstate, wire.T_OK,
                {"__token": bound.token, "__sid": bound.session.id}, rid,
            )
            return bound, False

        if ftype == wire.T_HEALTH:
            # Control-plane scrape (DESIGN.md §14): answered inline on the
            # connection loop — no session binding, no worker-thread spawn —
            # so supervisor heartbeats never queue behind mid-FETCH
            # data-plane threads. The merged stats snapshot rides as one
            # JSON string because the ALPK codec is scalars-and-flat-lists
            # by design; `__seq` is duplicated as a scalar so a scraper can
            # reject stale or reordered replies without parsing the blob.
            snap = self.engine.stats()
            self._reply(
                conn, cstate, wire.T_OK,
                {
                    "__stats_json": json.dumps(snap),
                    "__seq": int(snap["engine"]["snapshot_seq"]),
                    "__uptime_s": float(snap["engine"]["uptime_s"]),
                },
                rid,
            )
            return bound, False

        if bound is None:
            raise SessionError(
                f"frame {wire.FRAME_NAMES.get(ftype, ftype)} before CONNECT/HELLO bound a session"
            )
        core = bound.core

        if ftype == wire.T_SEND:
            # The array body follows on the socket: it must be read on this
            # thread (frames are sequential), shard-direct when the frame
            # declares a geometry this session's layout agrees with.
            with TraceAnnotation("al.server.send", rid=rid):
                arr, nread = self._recv_send_payload(conn, bound, rid)
                self.stats["bytes_in"] += nread
                # The offload planner wants a host payload for the content
                # store: the received bytes are already private to this send,
                # so the store adopts them as they lie — staging slabs and
                # all — with no host copy (plain sends keep none at all).
                payload = arr if bool(req.get("__has_payload")) else None
                fut = core._local_submit_send(
                    arr,
                    name=str(req.get("__name") or ""),
                    block=bool(req.get("__block")),
                    key=None,
                    payload=payload,
                )
                self._reply(conn, cstate, wire.T_OK, {"__ticket": bound.ticket(fut)}, rid)

        elif ftype == wire.T_RUN:
            with TraceAnnotation("al.server.run", rid=rid):
                dec = wire.decode_run_request(
                    req, future_of=bound.future, handle_of=self._lenient_handle(bound)
                )
                fut = core._local_submit_run(
                    dec["library"],
                    dec["routine"],
                    dec["args"],
                    dec["params"],
                    block=dec["block"],
                    out_shapes=dec["out_shapes"],
                    out_dtype=dec["out_dtype"],
                )
                self._reply(conn, cstate, wire.T_OK, {"__ticket": bound.ticket(fut)}, rid)

        elif ftype == wire.T_COLLECT:
            with TraceAnnotation("al.server.collect", rid=rid):
                target = self._target(bound, req)
                fut = core._local_submit_collect(target)
                self._reply(conn, cstate, wire.T_OK, {"__ticket": bound.ticket(fut)}, rid)

        elif ftype == wire.T_FETCH:
            fut = bound.future(int(req["__ticket"]))
            timeout = req.get("__timeout")
            self._spawn(
                cstate,
                lambda: self._do_fetch(
                    conn, cstate, bound, fut,
                    None if timeout is None else float(timeout), rid,
                ),
                label="wire-fetch",
            )

        elif ftype == wire.T_FREE:
            with TraceAnnotation("al.server.free", rid=rid):
                target = self._target(bound, req)
                fut = core._local_free_async(target)
                self._reply(conn, cstate, wire.T_OK, {"__ticket": bound.ticket(fut)}, rid)

        elif ftype == wire.T_BARRIER:
            timeout = req.get("__timeout")
            self._spawn(
                cstate,
                lambda: self._do_barrier(
                    conn, cstate, bound,
                    None if timeout is None else float(timeout), rid,
                ),
                label="wire-barrier",
            )

        elif ftype == wire.T_REGISTER:
            core._local_register_library(str(req["__name"]), str(req["__spec"]))
            self._reply(conn, cstate, wire.T_OK, {}, rid)

        elif ftype == wire.T_CLOSE:
            self._release(bound, why="client close")
            self._reply(conn, cstate, wire.T_OK, {}, rid)
            return bound, True

        else:
            raise SessionError(f"unknown wire frame type 0x{ftype:02x}")
        return bound, False

    # -- SEND: shard-direct receive (DESIGN.md §13) ---------------------------
    def _recv_send_payload(self, conn: socket.socket, bound: _Bound, rid: Optional[int]):
        """The ARRAY body following a SEND → (array-or-StagedShards, bytes).

        Frames declaring shard-aligned chunking decode straight into staging
        slabs from the governor's pool, with eager per-shard ``device_put``
        on the transfer ring when no HBM budget gates admission; anything
        else (v2 frames without geometry, geometry the session's layout no
        longer matches) reassembles into the one buffer that becomes the
        payload array. Mid-stream failure returns every unclaimed slab to
        the pool and re-raises — no handle exists yet, so nothing is
        half-admitted."""
        ftype, meta, n0 = wire.recv_frame(conn)
        if ftype != wire.T_ARRAY:
            raise ParameterError(
                f"SEND must be followed by an ARRAY frame, got "
                f"{wire.FRAME_NAMES.get(ftype, ftype)}"
            )
        with TraceAnnotation("al.wire.recv", rid=rid, nbytes=int(meta.get("__nbytes") or 0)):
            arr, nbody = self._recv_send_body(conn, bound, meta)
        return arr, n0 + nbody

    def _recv_send_body(self, conn: socket.socket, bound: _Bound, meta: Dict[str, Any]):
        from repro.core.relayout import shard_geometry

        if meta.get("__shards") and not bound.core.engine_layout.cyclic:
            sess = bound.session
            shape = (int(meta["__rows"]), int(meta["__cols"]))
            geom = shard_geometry(
                shape, meta["__dtype"], bound.core.client_layout, sess.mesh
            )
            if (
                geom is not None
                and geom.n_shards == int(meta["__shards"])
                and geom.shard_rows == int(meta["__srows"])
            ):
                mg = sess.memgov
                recv = wire.ShardStreamReceiver(
                    meta, geom,
                    pool=mg.staging, ring=mg.transfer_ring(), eager=mg.unbudgeted(),
                )
                try:
                    nbody = recv.recv_body(conn)
                except BaseException:
                    recv.abort()  # idempotent: pool release dedups by identity
                    raise
                staged = recv.staged
                staged.on_assembled = self._record_overlap
                self.stats["shard_direct_receives"] += 1
                return staged, nbody
        arr, nbody = wire.recv_array_body(conn, meta)
        self.stats["reassembly_receives"] += 1
        return arr, nbody

    def _record_overlap(self, staged) -> None:
        ratio = staged.overlap_ratio()
        if ratio is None:
            return
        put = sum(e - s for s, e in staged.put_windows)
        self.stats["put_ns"] += int(put * 1e9)
        self.stats["overlap_ns"] += int(ratio * put * 1e9)

    # -- FETCH: streamed send (DESIGN.md §13) ---------------------------------
    def _do_fetch(
        self,
        conn: socket.socket,
        cstate: _ConnState,
        bound: _Bound,
        fut: AlFuture,
        timeout: Optional[float],
        rid: Optional[int],
    ) -> None:
        try:
            val = fut.result(timeout)
        except BaseException as exc:  # noqa: BLE001 — crosses as an ERR frame
            try:
                self._reply(conn, cstate, wire.T_ERR, wire.error_payload(exc), rid)
            except (ConnectionError, OSError):
                pass
            return
        try:
            with TraceAnnotation("al.wire.fetch", rid=rid, nbytes=getattr(val, "nbytes", 0)):
                self._send_fetch_array(conn, cstate, bound, val, rid)
        except (ConnectionError, OSError):
            pass  # peer vanished; the connection loop owns teardown

    def _do_barrier(
        self,
        conn: socket.socket,
        cstate: _ConnState,
        bound: _Bound,
        timeout: Optional[float],
        rid: Optional[int],
    ) -> None:
        try:
            bound.session.drain(timeout)
        except BaseException as exc:  # noqa: BLE001
            try:
                self._reply(conn, cstate, wire.T_ERR, wire.error_payload(exc), rid)
            except (ConnectionError, OSError):
                pass
            return
        try:
            self._reply(conn, cstate, wire.T_OK, {}, rid)
        except (ConnectionError, OSError):
            pass

    def _send_fetch_array(
        self,
        conn: socket.socket,
        cstate: _ConnState,
        bound: _Bound,
        val: Any,
        rid: Optional[int],
    ) -> None:
        slabs = _row_slabs(val)
        if slabs is None:
            with TraceAnnotation("al.device.get", nbytes=getattr(val, "nbytes", 0)):
                out = np.asarray(val)
            self.stats["gathered_fetches"] += 1
            header, chunks, framed = wire.encode_array(out)
            if rid is not None:
                # Re-pack with the rid folded into the ARRAY meta so the
                # client reader can correlate the reply.
                meta = wire.array_header(out)
                meta["__rid"] = int(rid)
                header = wire.pack_frame(wire.T_ARRAY, meta)
                framed = len(header) + sum(8 + len(c) for c in chunks)
            bufs: List[Any] = [header]
            for c in chunks:
                bufs.append(struct.pack("<Q", len(c)))
                bufs.append(c)
            with cstate.wlock:
                wire.sendmsg_all(conn, bufs, self.stats)
            self.stats["bytes_out"] += framed
            return

        # Streamed path: slab i+1's device_get overlaps slab i's socket
        # write. The meta is computable from shard indices alone — no gather.
        self.stats["streamed_fetches"] += 1
        rows, cols = int(val.shape[0]), int(val.shape[1])
        itemsize = np.dtype(val.dtype).itemsize
        slab_bytes = [(stop - start) * cols * itemsize for (start, stop, _sh) in slabs]
        meta = {
            "__rows": rows,
            "__cols": cols,
            "__dtype": np.dtype(val.dtype).name,
            "__nbytes": rows * cols * itemsize,
            "__pad_r": 0,
            "__pad_c": 0,
            "__chunks": sum(-(-b // wire.CHUNK_BYTES) for b in slab_bytes if b),
        }
        if rid is not None:
            meta["__rid"] = int(rid)
        header = wire.pack_frame(wire.T_ARRAY, meta)
        ring = bound.session.memgov.transfer_ring()

        def launch(i: int):
            ev = threading.Event()
            box: Dict[str, np.ndarray] = {}

            def job() -> None:
                try:
                    with TraceAnnotation("al.device.get", nbytes=slab_bytes[i]):
                        box["v"] = np.asarray(slabs[i][2].data)
                finally:
                    ev.set()

            if not ring.try_submit(job):
                job()
            return ev, box

        sent = len(header)
        pending = launch(0)
        with cstate.wlock:
            conn.sendall(header)
            for i in range(len(slabs)):
                ev, box = pending
                ev.wait()
                cur = box["v"]
                if i + 1 < len(slabs):
                    pending = launch(i + 1)  # overlap next device_get
                data = memoryview(np.ascontiguousarray(cur)).cast("B")
                bufs = []
                for off in range(0, data.nbytes, wire.CHUNK_BYTES):
                    c = data[off : off + wire.CHUNK_BYTES]
                    bufs.append(struct.pack("<Q", c.nbytes))
                    bufs.append(c)
                if bufs:
                    sent += wire.sendmsg_all(conn, bufs, self.stats)
        self.stats["bytes_out"] += sent

    def _connect(self, req: Dict[str, Any]) -> _Bound:
        from repro.core.client import ClientCore

        n_keys = int(req.get("__n_keys") or 0)
        datasets = [
            (
                tuple(req[f"__k{i}_shape"]),
                str(req[f"__k{i}_dtype"]),
                str(req[f"__k{i}_sha"]),
            )
            for i in range(n_keys)
        ]
        from repro.core.scheduler import PlacementRequest

        grid = req.get("__grid")
        workers = req.get("__workers")
        if "__deadline" in req or "__priority" in req:
            deadline = req.get("__deadline")
            placement = PlacementRequest(
                workers=None if workers is None else int(workers),
                grid=None if grid is None else tuple(int(d) for d in grid),
                priority=int(req.get("__priority") or 0),
                affinity=tuple(datasets),
                deadline=None if deadline is None else float(deadline),
                allow_shared=bool(req.get("__allow_shared", True)),
            )
        else:
            # v1 client (pre-scheduler wire): __queue/__timeout semantics.
            timeout = req.get("__timeout")
            placement = PlacementRequest(
                workers=None if workers is None else int(workers),
                grid=None if grid is None else tuple(int(d) for d in grid),
                affinity=tuple(datasets),
                deadline=(
                    (None if timeout is None else float(timeout))
                    if bool(req.get("__queue"))
                    else 0.0
                ),
            )
        session = self.engine.connect(
            name=str(req.get("__name") or "app"),
            hbm_budget=req.get("__hbm_budget"),
            placement=placement,
        )
        core = ClientCore._over_session(
            self.engine,
            session,
            layout_by_name(str(req.get("__clayout") or "row")),
            layout_by_name(str(req.get("__elayout") or "grid")),
        )
        b = _Bound(uuid.uuid4().hex, session, core)
        with self._lock:
            self._bound[b.token] = b
        return b

    def _target(self, bound: _Bound, req: Dict[str, Any]):
        """COLLECT/FREE target: a ticket naming an in-flight future, or a
        HandleRef resolved against the session table — leniently, so an
        unknown/foreign handle fails inside the task (the classic surface),
        not at the RPC."""
        if "__ticket" in req:
            return bound.future(int(req["__ticket"]))
        return self._lenient_handle(bound)(req["__h"])

    def _lenient_handle(self, bound: _Bound):
        def resolve(ref: HandleRef):
            live = bound.session.handles.get(ref.id)
            return live if live is not None else ref
        return resolve


def _row_slabs(val: Any) -> Optional[List[Tuple[int, int, Any]]]:
    """Contiguous full-width row slabs covering ``val``, in row order, or
    None when the array cannot stream (host array, non-2D, column-sharded,
    strided, empty). Replicated shards dedup by start row — one copy crosses
    the wire."""
    import jax

    if not isinstance(val, jax.Array) or val.ndim != 2 or val.shape[0] == 0:
        return None
    try:
        shards = list(val.addressable_shards)
    except Exception:  # pragma: no cover - exotic arrays
        return None
    rows, cols = int(val.shape[0]), int(val.shape[1])
    by_start: Dict[int, Tuple[int, Any]] = {}
    for sh in shards:
        idx = sh.index
        r = idx[0] if len(idx) >= 1 else slice(None)
        c = idx[1] if len(idx) >= 2 else slice(None)
        if not isinstance(r, slice) or not isinstance(c, slice):
            return None
        if c.start not in (None, 0) or c.stop not in (None, cols) or c.step not in (None, 1):
            return None  # column-sharded: no contiguous row slabs
        if r.step not in (None, 1):
            return None
        start = r.start or 0
        stop = rows if r.stop is None else int(r.stop)
        if start not in by_start:  # replicas: first copy wins
            by_start[start] = (stop, sh)
    out: List[Tuple[int, int, Any]] = []
    pos = 0
    while pos < rows:
        got = by_start.get(pos)
        if got is None:
            return None  # gap: the shards do not partition the rows
        stop, sh = got
        if stop <= pos:
            return None
        out.append((pos, stop, sh))
        pos = stop
    return out if pos == rows else None


class _TcpCollectFuture(AlFuture):
    """Client half of a wire collect: COLLECT enqueued engine-side (ticket),
    bytes pulled through FETCH on first ``result()``. ``done()``/callbacks
    observe the engine-side future (in-process parity, see module doc);
    the payload itself always crosses the socket exactly once."""

    def __init__(self, transport: "TcpTransport", ticket: int, engine_fut: AlFuture):
        super().__init__(label=f"collect:tcp:{ticket}")
        self._transport = transport
        self._ticket = ticket
        self._engine_fut = engine_fut
        self._fetch_lock = threading.Lock()

    def done(self) -> bool:
        return self._event.is_set() or self._engine_fut.done()

    def add_done_callback(self, fn) -> None:
        if self._event.is_set():
            fn(self)
            return
        self._engine_fut.add_done_callback(lambda _parent: fn(self))

    def _ensure_fetched(self, timeout: Optional[float]) -> None:
        """Pull the payload once. Task failures memoize into this future;
        a wait timeout (server-side ``result(timeout)`` expiring) raises
        without memoizing, so a later call can still succeed."""
        with self._fetch_lock:
            if self._event.is_set():
                return
            try:
                arr = self._transport._fetch(self._ticket, timeout)
            except TaskError as exc:
                if "not resolved within" in str(exc):
                    raise  # transient wait timeout crossing as TaskError
                self._set_exception(exc)
            except BaseException as exc:  # noqa: BLE001 — future API contract
                self._set_exception(exc)
            else:
                self._set_result(arr)

    def exception(self, timeout: Optional[float] = None) -> Optional[BaseException]:
        self._ensure_fetched(timeout)
        return super().exception(timeout)

    def result(self, timeout: Optional[float] = None) -> Any:
        self._ensure_fetched(timeout)
        return super().result(timeout)


class _WireSocket(socket.socket):
    """Client socket whose ``close()`` severs the TCP connection *now*.

    The v2 transport keeps a reader thread blocked in ``recv`` on this
    socket. A plain ``close()`` only drops the fd — the kernel keeps the
    connection (and never sends FIN) while the blocked syscall holds the
    file description, so the server would never observe the disconnect.
    ``shutdown`` acts on the connection itself: FIN goes out immediately and
    the blocked reader wakes with EOF. This is also what keeps the test
    idiom ``transport._sock.close()`` meaning "client process died"."""

    def close(self):  # noqa: D102 — see class doc
        try:
            self.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass  # never connected / already reset
        super().close()


class _Waiter:
    """One in-flight RPC's reply slot, filled by the reader thread."""

    __slots__ = ("event", "kind", "reply", "array", "error")

    def __init__(self):
        self.event = threading.Event()
        self.kind = ""
        self.reply: Dict[str, Any] = {}
        self.array: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None

    def deliver(self, kind: str, reply: Dict[str, Any], array) -> None:
        self.kind, self.reply, self.array = kind, reply, array
        self.event.set()

    def fail(self, exc: BaseException) -> None:
        self.error = exc
        self.event.set()

    def wait(self):
        self.event.wait()
        if self.error is not None:
            raise self.error
        return self.kind, self.reply, self.array


class TcpTransport(Transport):
    """Client-side wire: the five verbs over one localhost TCP connection.

    One connection per client core (sessions stay independently socketed, so
    cross-session overlap survives the wire). Since v2 the connection is
    **multi-in-flight**: every request carries a ``__rid``, a reader thread
    demultiplexes correlated replies, and concurrent callers pipeline on the
    socket instead of serializing behind one lock-held round trip. On a
    broken socket, a transport holding a session token transparently
    reconnects (HELLO + token) exactly once per failure epoch and retries
    the RPC — the server side of the story is ``EngineServer`` linger.
    """

    name = "tcp"

    def __init__(self, server: Optional[EngineServer] = None):
        self._server = server
        self._sock: Optional[socket.socket] = None
        self._wlock = threading.RLock()  # socket writes + waiter registration
        self._conn_lock = threading.RLock()  # reconnects are single-flight
        self._reconnect_epoch = 0
        self._waiters: Dict[int, _Waiter] = {}
        self._rids = itertools.count(1)
        self.token: Optional[str] = None
        self.bytes_sent = 0
        self.bytes_received = 0
        self.frames = 0
        self.counters: Dict[str, int] = {"vectored_writes": 0}
        self._max_inflight = 0

    # -- connection management ----------------------------------------------
    @property
    def server(self) -> EngineServer:
        if self._server is None:
            raise SessionError("TcpTransport has no server; open_session first")
        return self._server

    def _dial(self) -> None:
        sock = _WireSocket(socket.AF_INET, socket.SOCK_STREAM)
        try:
            sock.connect(self.server.address)
        except BaseException:
            sock.close()
            raise
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock = sock
        threading.Thread(
            target=self._read_loop,
            args=(self._sock,),
            daemon=True,
            name="wire-client-reader",
        ).start()

    def _read_loop(self, sock: socket.socket) -> None:
        """Reply demultiplexer: one per socket epoch. ARRAY bodies are read
        inline (frames are sequential on the wire); socket death fails every
        waiter with ConnectionError so their RPCs can retry on a fresh
        socket."""
        while True:
            try:
                rtype, reply, nread = wire.recv_frame(sock)
                self.bytes_received += nread
                array = None
                if rtype == wire.T_ARRAY:
                    nbytes = int(reply.get("__nbytes") or 0)
                    with TraceAnnotation("al.wire.read", rid=reply.get("__rid"), nbytes=nbytes):
                        array, nbody = wire.recv_array_body(sock, reply)
                    self.bytes_received += nbody
            except BaseException as exc:  # noqa: BLE001 — fail all, exit
                err = exc if isinstance(exc, (ConnectionError, OSError)) else (
                    ConnectionError(f"wire reader failed: {exc}")
                )
                with self._wlock:
                    waiters = list(self._waiters.values())
                    self._waiters.clear()
                for w in waiters:
                    w.fail(err)
                return
            rid = reply.get("__rid")
            with self._wlock:
                w = self._waiters.pop(int(rid), None) if rid is not None else None
            if w is None:
                continue  # stale reply from before a reconnect
            kind = {wire.T_ERR: "err", wire.T_ARRAY: "array"}.get(rtype, "ok")
            w.deliver(kind, reply, array)

    def open_session(self, core, kwargs):
        if self._server is None:
            self._server = ensure_server(core.engine)
        self._dial()
        try:
            self._rpc_once(
                wire.T_HELLO, {"__token": None, "__version": wire.WIRE_VERSION}
            )
            reply = self._rpc_once(wire.T_CONNECT, self._connect_payload(core, kwargs))
        except BaseException:
            self._close_sock()
            raise
        self.token = str(reply["__token"])
        return self.server.session_object(self.token)

    def _connect_payload(self, core, kwargs) -> Dict[str, Any]:
        from repro.core.engine import _dataset_keys
        from repro.core.scheduler import PlacementRequest

        # CONNECT carries the declarative PlacementRequest (DESIGN.md §12).
        # Affinity payloads are hashed to content keys client-side — same
        # gate the engine applies (content_key reads every byte) — so the
        # wire never ships dataset bytes at connect time.
        request: PlacementRequest = kwargs.get("placement") or PlacementRequest(deadline=0.0)
        affinity = request.affinity or ()
        keys = _dataset_keys(affinity) if affinity and core.engine.residents.enabled else []
        payload: Dict[str, Any] = {
            "__version": wire.WIRE_VERSION,
            "__name": kwargs.get("name") or "app",
            "__workers": request.workers,
            "__grid": None if request.grid is None else [int(d) for d in request.grid],
            "__hbm_budget": kwargs.get("hbm_budget"),
            "__priority": int(request.priority),
            "__deadline": None if request.deadline is None else float(request.deadline),
            "__allow_shared": bool(request.allow_shared),
            "__clayout": core.client_layout.name,
            "__elayout": core.engine_layout.name,
            "__n_keys": len(keys),
        }
        for i, (shape, dtype, sha) in enumerate(keys):
            payload[f"__k{i}_shape"] = [int(d) for d in shape]
            payload[f"__k{i}_dtype"] = str(dtype)
            payload[f"__k{i}_sha"] = str(sha)
        return payload

    def reconnect(self) -> None:
        """Re-dial and re-bind the session token (requires server linger or
        a still-open server binding)."""
        if self.token is None:
            raise SessionError("no session token to reconnect with")
        self._close_sock()
        self._dial()
        self._rpc_once(
            wire.T_HELLO, {"__token": self.token, "__version": wire.WIRE_VERSION}
        )

    def _recover(self, epoch: int) -> None:
        """Single-flight reconnect: the first RPC to observe the failure
        epoch re-dials; concurrent failures wait on the lock, see the bumped
        epoch, and go straight to their retry on the fresh socket."""
        with self._conn_lock:
            if self._reconnect_epoch != epoch:
                return  # another thread already reconnected
            if self.token is None or not self.server.has_session(self.token):
                raise SessionError(
                    "wire connection lost and session no longer bound "
                    "(server released it on disconnect)"
                ) from None
            self.reconnect()
            self._reconnect_epoch = epoch + 1

    def _close_sock(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    # -- RPC core ------------------------------------------------------------
    def _rpc(
        self,
        ftype: int,
        payload: Dict[str, Any],
        array: Optional[np.ndarray] = None,
        expect_array: bool = False,
        geom=None,
    ):
        epoch = self._reconnect_epoch
        try:
            return self._rpc_once(ftype, payload, array, expect_array, geom)
        except (ConnectionError, OSError):
            # Broken pipe / reset / EOF mid-RPC. With a token and a server
            # that still knows it (linger window, or the drop hit us before
            # the server noticed), re-bind and retry once.
            self._recover(epoch)
            return self._rpc_once(ftype, payload, array, expect_array, geom)

    def _rpc_once(self, ftype, payload, array=None, expect_array=False, geom=None):
        rid = next(self._rids)
        waiter = _Waiter()
        with self._wlock:
            sock = self._sock
            if sock is None:
                raise ConnectionError("transport socket is closed")
            self._waiters[rid] = waiter
            self._max_inflight = max(self._max_inflight, len(self._waiters))
            try:
                self.frames += 1
                with TraceAnnotation(
                    "al.wire.write",
                    rid=rid,
                    frame=wire.FRAME_NAMES.get(ftype, ftype),
                    nbytes=0 if array is None else array.nbytes,
                ):
                    self.bytes_sent += wire.send_frame(sock, ftype, {**payload, "__rid": rid})
                    if array is not None:
                        self.bytes_sent += wire.send_array(
                            sock, array, geom=geom, counters=self.counters
                        )
            except BaseException:
                self._waiters.pop(rid, None)
                raise
        kind, reply, arr = waiter.wait()  # ConnectionError here → _rpc retries
        if kind == "err":
            raise wire.exception_from_payload(reply)
        if kind == "array":
            if not expect_array:
                raise SessionError("unexpected ARRAY reply")
            return arr
        if expect_array:
            raise SessionError(
                f"expected ARRAY reply, got {wire.FRAME_NAMES.get(ftype, ftype)}"
            )
        return reply

    def _fetch(self, ticket: int, timeout: Optional[float]):
        return self._rpc(
            wire.T_FETCH,
            {"__ticket": ticket, "__timeout": timeout},
            expect_array=True,
        )

    def _take(self, reply: Dict[str, Any]) -> AlFuture:
        ticket = int(reply["__ticket"])
        fut = self.server.take_future(self.token, ticket)
        fut._wire_ticket = ticket
        return fut

    @staticmethod
    def _wire_ref(obj: Any) -> Optional[int]:
        return getattr(obj, "_wire_ticket", None)

    def _ticket_for(self, fut: AlFuture) -> int:
        """The wire name for a future: the ticket the server minted for it,
        or a fresh registration for derived futures (`.then` projections)
        that never crossed as an RPC reply."""
        t = self._wire_ref(fut)
        if t is None:
            t = self.server.register_future(self.token, fut)
            fut._wire_ticket = t
        return t

    # -- the verbs -----------------------------------------------------------
    def submit_send(self, core, array, *, name, block, key=None, payload=None):
        # The payload doubles as the attach fallback server-side, so the
        # bytes always cross (socket bytes are not bridge bytes: the session
        # counters that the parity check compares are engine-side). Frames
        # go shard-aligned whenever the client layout has a row-slab
        # geometry, letting the server decode shard-direct.
        from repro.core.relayout import shard_geometry

        host = np.asarray(array)
        geom = None
        sess = getattr(core, "session", None)
        if sess is not None and not core.engine_layout.cyclic:
            geom = shard_geometry(
                host.shape, host.dtype, core.client_layout, sess.mesh
            )
        reply = self._rpc(
            wire.T_SEND,
            {"__name": name, "__block": block, "__has_payload": payload is not None},
            array=host,
            geom=geom,
        )
        return self._take(reply)

    def submit_run(self, core, library, routine, args, params, *, block, out_shapes, out_dtype):
        try:
            payload = wire.encode_run_request(
                library,
                routine,
                args,
                params,
                block=block,
                out_shapes=out_shapes,
                out_dtype=out_dtype,
                ticket_of=self._ticket_for,
            )
            wire.pack_frame(wire.T_RUN, payload)  # prove the args frame
        except Exception as exc:  # noqa: BLE001 — unserializable args fail the
            # future, not the call site (loopback parity: the in-process path
            # hits the same codec inside the task).
            fut = AlFuture(label=f"run:{library}.{routine}:reject")
            fut._set_exception(exc)
            return fut
        return self._take(self._rpc(wire.T_RUN, payload))

    def submit_collect(self, core, h):
        req = self._collect_target(h)
        reply = self._rpc(wire.T_COLLECT, req)
        ticket = int(reply["__ticket"])
        return _TcpCollectFuture(self, ticket, self.server.take_future(self.token, ticket))

    def free(self, core, h):
        return self._take(self._rpc(wire.T_FREE, self._collect_target(h)))

    def _collect_target(self, h) -> Dict[str, Any]:
        if isinstance(h, _TcpCollectFuture):
            return {"__ticket": h._ticket}
        if isinstance(h, AlFuture):
            return {"__ticket": self._ticket_for(h)}
        return {"__h": h}  # AlMatrix/HandleRef: the codec frames it

    def barrier(self, core, timeout):
        self._rpc(wire.T_BARRIER, {"__timeout": timeout})

    def register_library(self, core, name, spec):
        self._rpc(wire.T_REGISTER, {"__name": name, "__spec": spec})
        return core.session.libraries[name]

    def close_session(self, core):
        try:
            self._rpc(wire.T_CLOSE, {})
        except (SessionError, ConnectionError, OSError):
            # Socket already dead: the server's disconnect path (or linger
            # expiry) owns the release; make it deterministic here.
            if self.token is not None and self.server.has_session(self.token):
                self.server._release(self.server._require(self.token), why="client stop")
        finally:
            self._close_sock()

    def wire_stats(self):
        return {
            "bytes_sent": self.bytes_sent,
            "bytes_received": self.bytes_received,
            "frames": self.frames,
            "vectored_writes": self.counters.get("vectored_writes", 0),
            "shard_direct_receives": 0,  # receives happen server-side
            "reassembly_receives": 0,
            "inflight": len(self._waiters),
            "max_inflight": self._max_inflight,
        }
