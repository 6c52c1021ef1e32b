"""Wire framing + the Transport seam (DESIGN.md §11).

The paper's deployment is a client/server split: Spark executors talk to an
Alchemist server process over sockets, with scalar metadata in serialized
``Parameters`` frames and matrix payloads in chunked worker-to-worker
transfers (§3.3/§3.5). This module is that boundary for the reproduction:

- **ALWF control frames** — ``b"ALWF" + type(u8) + length(u64)`` followed by
  a hardened ALPK parameter frame (:mod:`repro.core.params`). Every verb of
  the protocol (CONNECT/SEND/RUN/COLLECT/...) is one control frame; replies
  are OK/ERR/ARRAY frames. Malformed bytes surface as
  :class:`~repro.core.errors.ParameterError`, which the server maps to an
  ERR reply instead of crashing its loop.
- **Array framing** — an ARRAY control frame carrying dtype/shape/pad
  metadata, followed by ``__chunks`` length-prefixed raw-byte chunks. The
  encoder hands out ``memoryview`` chunks over the source buffer (zero-copy
  on the send side); the decoder reassembles into one contiguous buffer.
- **The Transport protocol** — extracted from ``ClientCore``'s
  ``_submit_send/_submit_run/_submit_collect/free/barrier`` call sites.
  :class:`LoopbackTransport` routes the in-process path through the same
  array encode/decode, so every existing test doubles as a wire test;
  ``repro.serve.wire.TcpTransport`` speaks the same frames over a localhost
  socket to an :class:`~repro.serve.wire.EngineServer`.

Transport selection: ``connect(transport=...)`` / ``ClientCore(transport=
...)`` take an instance or a name; the ``REPRO_TRANSPORT`` environment
variable (``loopback`` | ``tcp``) sets the default for an entire run, which
is how CI executes the whole tier-1 suite over a real socket.
"""

from __future__ import annotations

import os
import socket
import struct
from typing import TYPE_CHECKING, Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import params as params_codec
from repro.core.errors import ParameterError, SessionError, TaskError
from repro.core.expr import raw_bytes
from repro.core.futures import AlFuture
from repro.core.payload import SlabPayload, aliases_host, join_rows

if TYPE_CHECKING:  # pragma: no cover - typing only
    from repro.core.client import ClientCore
    from repro.core.session import Session

WIRE_MAGIC = b"ALWF"
_HEADER = struct.Struct("<4sBQ")

#: Frame-format version, carried in HELLO/CONNECT. v2 (PR 9) added
#: rid-correlated multi-in-flight replies and shard-aligned array framing;
#: a v1 client greeting a v2 server gets a typed ERR naming both versions
#: (never garbage), because the server checks this before anything else.
WIRE_VERSION = 2

# Control-frame types (requests).
T_HELLO = 0x01
T_CONNECT = 0x02
T_SEND = 0x03
T_RUN = 0x04
T_COLLECT = 0x05
T_FETCH = 0x06
T_FREE = 0x07
T_BARRIER = 0x08
T_REGISTER = 0x09
T_CLOSE = 0x0A
T_HEALTH = 0x0B
# Replies.
T_OK = 0x20
T_ERR = 0x21
T_ARRAY = 0x22

FRAME_NAMES = {
    T_HELLO: "HELLO", T_CONNECT: "CONNECT", T_SEND: "SEND", T_RUN: "RUN",
    T_COLLECT: "COLLECT", T_FETCH: "FETCH", T_FREE: "FREE",
    T_BARRIER: "BARRIER", T_REGISTER: "REGISTER", T_CLOSE: "CLOSE",
    T_HEALTH: "HEALTH",
    T_OK: "OK", T_ERR: "ERR", T_ARRAY: "ARRAY",
}

# Array payloads cross in bounded chunks so neither side ever materializes
# a second full copy for framing (and a reader can account progress).
CHUNK_BYTES = 1 << 20

MAX_FRAME_BYTES = 1 << 24  # control frames are metadata; 16 MiB is hostile


# -- control frames ----------------------------------------------------------
def pack_frame(ftype: int, payload: Dict[str, Any]) -> bytes:
    body = params_codec.pack(payload)
    return _HEADER.pack(WIRE_MAGIC, ftype, len(body)) + body


def unpack_frame(buf: bytes) -> Tuple[int, Dict[str, Any]]:
    if len(buf) < _HEADER.size:
        raise ParameterError(f"truncated ALWF frame header ({len(buf)} bytes)")
    magic, ftype, n = _HEADER.unpack_from(buf, 0)
    if magic != WIRE_MAGIC:
        raise ParameterError("bad magic — not an ALWF wire frame")
    body = buf[_HEADER.size :]
    if len(body) != n:
        raise ParameterError(f"ALWF frame declares {n} payload bytes, has {len(body)}")
    return ftype, params_codec.unpack(body)


# -- socket helpers ----------------------------------------------------------
def recv_exact(sock: socket.socket, n: int) -> memoryview:
    """Read exactly ``n`` bytes or raise ConnectionError on EOF."""
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError(f"peer closed mid-frame ({got}/{n} bytes)")
        got += r
    return memoryview(buf)


def send_frame(sock: socket.socket, ftype: int, payload: Dict[str, Any]) -> int:
    data = pack_frame(ftype, payload)
    sock.sendall(data)
    return len(data)


# sendmsg iovec arrays are capped (IOV_MAX, typically 1024); stay far under
# it so one vectored write never has to be split by the kernel's limit.
_IOV_GROUP = 64


def sendmsg_all(sock: socket.socket, buffers: Sequence[Any], counters: Optional[Dict[str, int]] = None) -> int:
    """Write ``buffers`` with as few syscalls as possible (writev-style).

    Coalesces header + length prefixes + payload chunks into vectored
    ``sendmsg`` calls, looping on partial sends; falls back to ``sendall``
    per buffer where ``sendmsg`` is unavailable. ``counters`` (when given)
    gets its ``"vectored_writes"`` key bumped once per syscall batch."""
    views = [v for v in (memoryview(b).cast("B") for b in buffers) if v.nbytes]
    total = sum(v.nbytes for v in views)
    if not hasattr(sock, "sendmsg"):  # pragma: no cover - non-POSIX sockets
        for v in views:
            sock.sendall(v)
        return total
    for i in range(0, len(views), _IOV_GROUP):
        group = views[i : i + _IOV_GROUP]
        while group:
            sent = sock.sendmsg(group)
            if counters is not None:
                counters["vectored_writes"] = counters.get("vectored_writes", 0) + 1
            while group and sent >= group[0].nbytes:
                sent -= group[0].nbytes
                group = group[1:]
            if group and sent:  # partial write landed inside a view
                group[0] = group[0][sent:]
    return total


def recv_frame(sock: socket.socket) -> Tuple[int, Dict[str, Any], int]:
    """Read one control frame; returns (type, payload, framed bytes)."""
    head = recv_exact(sock, _HEADER.size)
    magic, ftype, n = _HEADER.unpack_from(head, 0)
    if magic != WIRE_MAGIC:
        raise ParameterError("bad magic — not an ALWF wire frame")
    if n > MAX_FRAME_BYTES:
        raise ParameterError(f"ALWF control frame declares {n} bytes (cap {MAX_FRAME_BYTES})")
    body = recv_exact(sock, n) if n else memoryview(b"")
    return ftype, params_codec.unpack(body), _HEADER.size + n


# -- array framing -----------------------------------------------------------
def array_header(arr, pads: Tuple[int, int] = (0, 0), geom=None) -> Dict[str, Any]:
    """Metadata frame for a 2D payload: dtype/shape describe the physical
    bytes on the wire; ``pads`` lets a sender ship a padded physical block
    whose receiver strips back to logical shape (DESIGN.md §7 padded sends).
    With ``geom`` (a :class:`~repro.core.relayout.ShardGeometry`) the frame
    declares shard-aligned chunking: ``__shards``/``__srows`` let the
    receiver decode each chunk straight into a per-shard staging slab."""
    meta = {
        "__rows": int(arr.shape[0]),
        "__cols": int(arr.shape[1]),
        "__dtype": np.dtype(arr.dtype).name,
        "__nbytes": int(arr.nbytes),
        "__pad_r": int(pads[0]),
        "__pad_c": int(pads[1]),
        "__chunks": max(1, -(-arr.nbytes // CHUNK_BYTES)) if arr.nbytes else 0,
    }
    if geom is not None:
        meta["__shards"] = int(geom.n_shards)
        meta["__srows"] = int(geom.shard_rows)
        meta["__chunks"] = sum(
            -(-geom.logical_bytes(j) // CHUNK_BYTES) for j in range(geom.n_shards)
        )
    return meta


def array_chunks(arr: np.ndarray, geom=None) -> List[memoryview]:
    """Zero-copy chunk views over the array's contiguous bytes. With ``geom``
    the chunk boundaries additionally break at shard-slab boundaries, so no
    chunk ever spans two destination shards (the stream is the same logical
    bytes either way — slabs are contiguous in row-major order)."""
    data = memoryview(np.ascontiguousarray(arr)).cast("B")
    if geom is None:
        return [data[i : i + CHUNK_BYTES] for i in range(0, len(data), CHUNK_BYTES)] or []
    itemsize, cols = geom.itemsize, arr.shape[1]
    chunks: List[memoryview] = []
    for s, e in geom.intervals:
        lo, hi = s * cols * itemsize, e * cols * itemsize
        chunks.extend(data[i : min(i + CHUNK_BYTES, hi)] for i in range(lo, hi, CHUNK_BYTES))
    return chunks


def encode_array(
    arr: np.ndarray, pads: Tuple[int, int] = (0, 0), geom=None
) -> Tuple[bytes, List[memoryview], int]:
    """(header frame, chunk views, total framed bytes) for one payload."""
    header = pack_frame(T_ARRAY, array_header(arr, pads, geom))
    chunks = array_chunks(arr, geom)
    framed = len(header) + sum(8 + len(c) for c in chunks)
    return header, chunks, framed


def decode_array(meta: Dict[str, Any], data) -> np.ndarray:
    """Inverse of :func:`encode_array` given the chunk bytes.

    ``data`` may be ``bytes``, ``bytearray``, or a ``memoryview`` — bytearray
    and memoryview input decode zero-copy (``np.frombuffer`` wraps the buffer
    in place), which is what keeps the loopback path and the receive side
    free of an extra contiguous copy for multi-chunk arrays."""
    try:
        dtype = np.dtype(meta["__dtype"])
    except (TypeError, KeyError) as exc:
        raise ParameterError(f"bad array frame dtype: {exc}") from None
    rows, cols = int(meta["__rows"]), int(meta["__cols"])
    nbytes = data.nbytes if isinstance(data, memoryview) else len(data)
    if rows * cols * dtype.itemsize != nbytes:
        raise ParameterError(
            f"array frame declares {rows}x{cols} {dtype.name} "
            f"({rows * cols * dtype.itemsize} bytes), got {nbytes} payload bytes"
        )
    arr = np.frombuffer(data, dtype=dtype).reshape(rows, cols)
    pr, pc = int(meta.get("__pad_r") or 0), int(meta.get("__pad_c") or 0)
    if pr or pc:
        arr = arr[: rows - pr, : cols - pc]
    return arr


def send_array(
    sock: socket.socket,
    arr: np.ndarray,
    pads: Tuple[int, int] = (0, 0),
    geom=None,
    counters: Optional[Dict[str, int]] = None,
) -> int:
    """Frame + stream one array: header, then length-prefixed chunks, all
    coalesced into vectored writes (one syscall covers many chunks) instead
    of the two ``sendall`` calls per chunk the v1 wire paid."""
    header, chunks, framed = encode_array(np.asarray(arr), pads, geom)
    bufs: List[Any] = [header]
    for c in chunks:
        bufs.append(struct.pack("<Q", len(c)))
        bufs.append(c)
    sendmsg_all(sock, bufs, counters)
    return framed


def recv_array_body(sock: socket.socket, meta: Dict[str, Any]) -> Tuple[np.ndarray, int]:
    """Chunks following an already-read ARRAY frame → (array, bytes read).

    Decodes in place over the receive buffer (no ``bytes()`` copy): this one
    allocation is the caller's final array, not a reassembly staging copy."""
    nbytes = int(meta["__nbytes"])
    buf = bytearray(nbytes)
    view = memoryview(buf)
    got = 0
    read = 0
    for _ in range(int(meta["__chunks"])):
        (n,) = struct.unpack("<Q", recv_exact(sock, 8))
        if got + n > nbytes:
            raise ParameterError(
                f"array chunks overflow declared size ({got + n} > {nbytes})"
            )
        recv_into(sock, view[got : got + n])
        got += n
        read += 8 + n
    if got != nbytes:
        raise ParameterError(f"array frame short: {got} of {nbytes} payload bytes")
    return decode_array(meta, view), read


def recv_into(sock: socket.socket, view: memoryview) -> None:
    """Fill ``view`` exactly from the socket, or raise ConnectionError."""
    got = 0
    n = view.nbytes
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError(f"peer closed mid-frame ({got}/{n} bytes)")
        got += r


def recv_array(sock: socket.socket) -> Tuple[np.ndarray, int]:
    ftype, meta, n0 = recv_frame(sock)
    if ftype != T_ARRAY:
        raise ParameterError(f"expected ARRAY frame, got {FRAME_NAMES.get(ftype, ftype)}")
    arr, n1 = recv_array_body(sock, meta)
    return arr, n0 + n1


# -- shard-direct staging (DESIGN.md §13) ------------------------------------
class StagedShards:
    """Receive-side result of a shard-direct stream: per-shard physical host
    slabs (drawn from the governor's staging pool) plus the geometry, with
    the host→device copies possibly already in flight on the transfer ring.

    Quacks enough like the logical ndarray (``shape``/``dtype``/``ndim``/
    ``__array__``) that validation, attach fallbacks, and the content store
    keep working; the send task assembles the sharded device array with
    ``jax.make_array_from_single_device_arrays`` — never a full-array
    reassembly copy. ``content_key()`` streams sha1 over the logical slab
    views for the same reason, and the resident store takes the slabs over
    as its payload (``adopt``) instead of copying them."""

    ndim = 2

    def __init__(self, geom, buffers: List[np.ndarray], pool=None):
        self.geom = geom
        self.buffers = buffers  # physical (shard_rows, cols) slabs
        self._pool = pool
        self._device: List[Optional[Any]] = [None] * geom.n_shards
        self._events = [None] * geom.n_shards  # threading.Event per eager put
        #: [(start, end)] wall-clock windows of completed device_put jobs and
        #: the socket-read window — the overlap-ratio instrumentation.
        self.put_windows: List[Tuple[float, float]] = []
        self.socket_window: Optional[Tuple[float, float]] = None
        #: Optional fn(staged) invoked once when device_array() completes —
        #: transports hook this to fold overlap/put timings into wire stats.
        self.on_assembled = None
        self._assembled = False
        #: the store payload these slabs became (``adopt``), if any.
        self.adopted: Optional[SlabPayload] = None

    @property
    def shape(self) -> Tuple[int, int]:
        return self.geom.shape

    @property
    def dtype(self):
        return np.dtype(self.geom.dtype)

    @property
    def nbytes(self) -> int:
        r, c = self.geom.shape
        return r * c * self.geom.itemsize

    def logical_slabs(self) -> List[np.ndarray]:
        """Per-shard views of the logical rows (pad slack excluded)."""
        out = []
        for j, (s, e) in enumerate(self.geom.intervals):
            out.append(self.buffers[j][: e - s])
        return out

    def __array__(self, dtype=None, copy=None):
        # Materialization fallback for consumers that need one array (a
        # stale geometry's classic send path): a full copy when there are
        # several shards, never on the shard-direct hot path.
        return join_rows(self.logical_slabs(), self.geom.shape, self.dtype, dtype, copy)

    def content_key(self) -> Tuple:
        """Streaming equivalent of :func:`repro.core.expr.content_key`: sha1
        over the logical slab bytes in row order, no reassembly copy."""
        import hashlib

        h = hashlib.sha1()
        with TraceAnnotation("al.store.key", nbytes=self.nbytes, side="staged"):
            for slab in self.logical_slabs():
                h.update(raw_bytes(slab))
        r, c = self.geom.shape
        return ((int(r), int(c)), str(self.dtype), h.hexdigest())

    def adopt(self) -> SlabPayload:
        """Hand the slabs to the resident store as its payload, uncopied.
        From here on ``dispose`` leaves them to the payload, which returns
        them to the pool when the last store entry holding it dies."""
        if self.adopted is None:
            self.adopted = SlabPayload(
                list(self.buffers), self.logical_slabs(), self.geom.shape, self.dtype, self._pool
            )
        return self.adopted

    def matches(self, layout, mesh) -> bool:
        return self.geom.matches(layout, mesh)

    # -- device assembly ------------------------------------------------------
    def _put(self, j: int) -> None:
        import time as _time

        import jax

        t0 = _time.perf_counter()
        with TraceAnnotation("al.device.put", nbytes=self.buffers[j].nbytes):
            arr = jax.device_put(self.buffers[j], self.geom.devices[j])
            arr.block_until_ready()
        self._device[j] = arr
        self.put_windows.append((t0, _time.perf_counter()))

    def device_array(self, sharding=None):
        """The staged client-layout device array: waits for in-flight ring
        puts, issues any remaining ones inline, and assembles the shards —
        no host-side reassembly. ``sharding`` is the client-layout sharding
        (callers in ``ClientCore`` pass the real one); absent, an equivalent
        row sharding is rebuilt from the geometry's device order."""
        import jax

        for j in range(self.geom.n_shards):
            ev = self._events[j]
            if ev is not None:
                ev.wait()
            if self._device[j] is None:
                self._put(j)
        if sharding is None:
            import jax.sharding as jsh

            mesh = jsh.Mesh(np.asarray(self.geom.devices), ("data",))
            sharding = jsh.NamedSharding(mesh, jsh.PartitionSpec("data", None))
        by_dev = {d: j for j, d in enumerate(self.geom.devices)}
        arrays = [
            self._device[by_dev[dev]]
            for dev in sharding.addressable_devices_indices_map(self.geom.physical_shape)
        ]
        out = jax.make_array_from_single_device_arrays(
            self.geom.physical_shape, sharding, arrays
        )
        if not self._assembled:
            self._assembled = True
            if self.on_assembled is not None:
                self.on_assembled(self)
        return out

    def overlap_ratio(self) -> Optional[float]:
        """Σ(put ∩ socket window) / Σ(put duration), None before finish()."""
        if self.socket_window is None or not self.put_windows:
            return None
        t0, t1 = self.socket_window
        put = sum(e - s for s, e in self.put_windows)
        if put <= 0:
            return None
        overlap = sum(max(0.0, min(e, t1) - max(s, t0)) for s, e in self.put_windows)
        return overlap / put

    def dispose(self, *check_arrays) -> None:
        """Return slabs to the staging pool — except any aliased by a live
        device array (CPU ``device_put`` is zero-copy; see ``aliases_host``).
        Adopted slabs stay with their store payload, which learns of the
        aliased ones so it never pools them either."""
        live = [self._device[j] for j in range(len(self.buffers))]
        live.extend(a for a in check_arrays if a is not None)
        if self.adopted is not None:
            self.adopted.pin_aliases(*live)
            return
        if self._pool is None:
            return
        for j, buf in enumerate(self.buffers):
            if buf is None:
                continue
            if any(a is not None and aliases_host(a, buf) for a in live):
                continue
            self._pool.release(buf)
            self.buffers[j] = buf  # kept readable for logical views
        # slabs stay referenced for logical reads; the pool guards against
        # double-acquire by identity, so a released-but-referenced slab is
        # only rewritten after this object is dropped by its consumer.


class ShardStreamReceiver:
    """Decodes a shard-aligned ARRAY body chunk-by-chunk into per-shard
    staging slabs, optionally firing a ``device_put`` per shard as its bytes
    land (overlapping socket reads with host→device copies).

    ``pool`` is the governor's staging pool (slab reuse across receives);
    ``ring`` a :class:`~repro.core.taskqueue.TransferExecutor` for the eager
    puts — when absent or full, puts run at assembly time instead."""

    def __init__(self, meta: Dict[str, Any], geom, pool=None, ring=None, eager: bool = True):
        import threading as _threading
        import time as _time

        self.geom = geom
        self.meta = meta
        self._ring = ring
        self._eager = eager and geom.shape[0] > 0
        self._threading = _threading
        self._time = _time
        slab = geom.slab_shape()
        dtype = np.dtype(geom.dtype)
        buffers = []
        for j, (s, e) in enumerate(geom.intervals):
            buf = pool.acquire(slab, dtype) if pool is not None else np.empty(slab, dtype)
            filled = e - s
            if filled < geom.shard_rows:
                buf[filled:] = 0  # pad slack: the fused-into-decode zero fill
            buffers.append(buf)
        self.staged = StagedShards(geom, buffers, pool=pool)
        self._shard = 0
        self._offset = 0  # bytes filled into the current shard's logical slab
        self._t0: Optional[float] = None
        self.read = 0

    def _advance_full_shards(self) -> None:
        while self._shard < self.geom.n_shards:
            want = self.geom.logical_bytes(self._shard)
            if self._offset < want:
                return
            self._complete(self._shard)
            self._shard += 1
            self._offset = 0

    def _complete(self, j: int) -> None:
        if not self._eager:
            return
        ev = self._threading.Event()
        self.staged._events[j] = ev

        def job(jj=j, ee=ev):
            try:
                self.staged._put(jj)
            finally:
                ee.set()

        if self._ring is None or not self._ring.try_submit(job):
            job()  # ring full: copy on this thread (still inside the window)

    def slab_view(self, n: int) -> memoryview:
        """A writable view of the next ``n`` bytes of the current shard's
        slab. Raises if the chunk would cross a shard boundary — the sender's
        framing contract."""
        while (
            self._shard < self.geom.n_shards
            and self.geom.logical_bytes(self._shard) == 0
        ):
            self._complete(self._shard)
            self._shard += 1
        if self._shard >= self.geom.n_shards:
            raise ParameterError("array chunks overflow declared shard layout")
        want = self.geom.logical_bytes(self._shard)
        if self._offset + n > want:
            raise ParameterError(
                f"chunk crosses shard boundary ({self._offset + n} > {want})"
            )
        buf = memoryview(self.staged.buffers[self._shard]).cast("B")
        return buf[self._offset : self._offset + n]

    def feed(self, data) -> None:
        """Decode one chunk (bytes/memoryview) into the staging slabs."""
        view = memoryview(data).cast("B")
        if self._t0 is None:
            self._t0 = self._time.perf_counter()
        self.slab_view(view.nbytes)[:] = view
        self._offset += view.nbytes
        self.read += view.nbytes
        self._advance_full_shards()

    def recv_body(self, sock: socket.socket) -> int:
        """Read the full shard-aligned body from ``sock`` (length-prefixed
        chunks, as framed by :func:`encode_array` with a geometry); returns
        framed bytes read."""
        if self._t0 is None:
            self._t0 = self._time.perf_counter()
        read = 0
        for _ in range(int(self.meta["__chunks"])):
            (n,) = struct.unpack("<Q", recv_exact(sock, 8))
            target = self.slab_view(n)
            recv_into(sock, target)
            self._offset += n
            read += 8 + n
            self.read += n
            self._advance_full_shards()
        self.finish()
        return read

    def finish(self) -> StagedShards:
        self._advance_full_shards()
        if self._shard < self.geom.n_shards or self._offset:
            self.abort()
            raise ParameterError(
                f"shard stream short: stopped in shard {self._shard} "
                f"of {self.geom.n_shards}"
            )
        t0 = self._t0 if self._t0 is not None else self._time.perf_counter()
        self.staged.socket_window = (t0, self._time.perf_counter())
        return self.staged

    def abort(self) -> None:
        """Mid-stream failure: hand unconsumed slabs straight back to the
        pool (shards already claimed by an eager put are left to the GC —
        their device arrays may alias the slab)."""
        pool = self.staged._pool
        if pool is None:
            return
        for j, buf in enumerate(self.staged.buffers):
            if self.staged._events[j] is None and self.staged._device[j] is None:
                pool.release(buf)


# -- error mapping -----------------------------------------------------------
def error_payload(exc: BaseException) -> Dict[str, Any]:
    return {"__etype": type(exc).__name__, "__emsg": str(exc)}


def exception_from_payload(payload: Dict[str, Any]) -> BaseException:
    """Reconstruct a wire error client-side: Alchemist errors by class name
    (their constructors are message-only by design), builtins likewise, and
    anything else degrades to TaskError carrying the original type name."""
    import builtins

    from repro.core import errors as errors_mod

    etype = str(payload.get("__etype") or "TaskError")
    msg = str(payload.get("__emsg") or "")
    cls = getattr(errors_mod, etype, None)
    if isinstance(cls, type) and issubclass(cls, errors_mod.AlchemistError):
        return cls(msg)
    bcls = getattr(builtins, etype, None)
    if isinstance(bcls, type) and issubclass(bcls, Exception):
        try:
            return bcls(msg)
        except TypeError:  # exotic constructor signature
            pass
    return TaskError(f"{etype}: {msg}")


# -- run-request framing -----------------------------------------------------
# A RUN request puts every argument through the codec: scalars/strings as
# themselves, matrix handles as HandleRefs, and in-flight futures as integer
# tickets the receiving side maps back through its ticket table.
def encode_run_request(
    library: str,
    routine: str,
    args: Tuple[Any, ...],
    params: Dict[str, Any],
    *,
    block: bool,
    out_shapes: Optional[Sequence] = None,
    out_dtype: Any = None,
    ticket_of=None,
) -> Dict[str, Any]:
    payload: Dict[str, Any] = {
        "__lib": library,
        "__routine": routine,
        "__block": block,
        "__n_args": len(args),
        "__out_dtype": None if out_dtype is None else np.dtype(out_dtype).name,
        "__n_shapes": -1 if out_shapes is None else len(out_shapes),
    }
    if out_shapes is not None:
        for i, s in enumerate(out_shapes):
            payload[f"__shape_{i}"] = None if s is None else [int(d) for d in s]
    for i, a in enumerate(args):
        if isinstance(a, AlFuture):
            if ticket_of is None:
                raise ParameterError(
                    f"run argument {i} is an in-flight future; this transport "
                    "cannot reference it"
                )
            payload[f"__t{i}"] = int(ticket_of(a))
        else:
            payload[f"__a{i}"] = a
    for k, v in params.items():
        if isinstance(v, AlFuture):
            if ticket_of is None:
                raise ParameterError(
                    f"run parameter {k!r} is an in-flight future; this "
                    "transport cannot reference it"
                )
            payload[f"__kt_{k}"] = int(ticket_of(v))
        else:
            payload[f"__kw_{k}"] = v
    return payload


def decode_run_request(
    payload: Dict[str, Any],
    *,
    future_of=None,
    handle_of=None,
) -> Dict[str, Any]:
    """Inverse of :func:`encode_run_request`. ``future_of(ticket)`` maps
    tickets back to futures; ``handle_of(ref)`` may eagerly resolve a
    HandleRef to its live AlMatrix (falling back to the ref itself keeps the
    classic lazy failure-at-execution semantics for unknown handles)."""
    n_args = int(payload["__n_args"])
    args: List[Any] = []
    for i in range(n_args):
        if f"__t{i}" in payload:
            args.append(future_of(int(payload[f"__t{i}"])))
        else:
            args.append(_maybe_handle(payload[f"__a{i}"], handle_of))
    params: Dict[str, Any] = {}
    for k, v in payload.items():
        if k.startswith("__kw_"):
            params[k[len("__kw_") :]] = _maybe_handle(v, handle_of)
        elif k.startswith("__kt_"):
            params[k[len("__kt_") :]] = future_of(int(v))
    n_shapes = int(payload["__n_shapes"])
    out_shapes = None
    if n_shapes >= 0:
        out_shapes = [
            None if payload[f"__shape_{i}"] is None else tuple(payload[f"__shape_{i}"])
            for i in range(n_shapes)
        ]
    out_dtype = payload["__out_dtype"]
    return {
        "library": payload["__lib"],
        "routine": payload["__routine"],
        "args": tuple(args),
        "params": params,
        "block": bool(payload["__block"]),
        "out_shapes": out_shapes,
        "out_dtype": None if out_dtype is None else np.dtype(out_dtype),
    }


def _maybe_handle(v: Any, handle_of) -> Any:
    if handle_of is not None and isinstance(v, params_codec.HandleRef):
        return handle_of(v)
    return v


# -- the Transport seam ------------------------------------------------------
class Transport:
    """Protocol extracted from ClientCore's submission call sites.

    A transport owns *how* the five verbs reach the engine; the engine-side
    semantics live in ``ClientCore._local_*``. Implementations must keep the
    verbs' error surfaces: fail-fast errors (unknown library, bad shapes)
    raise at the call site, execution errors fail the returned future.
    """

    name = "base"

    def open_session(self, core: "ClientCore", kwargs: Dict[str, Any]) -> "Session":
        raise NotImplementedError

    def submit_send(self, core, array, *, name, block, key=None, payload=None) -> AlFuture:
        raise NotImplementedError

    def submit_run(
        self, core, library, routine, args, params, *, block, out_shapes, out_dtype
    ) -> AlFuture:
        raise NotImplementedError

    def submit_collect(self, core, h) -> AlFuture:
        raise NotImplementedError

    def free(self, core, h) -> AlFuture:
        raise NotImplementedError

    def barrier(self, core, timeout: Optional[float]) -> None:
        raise NotImplementedError

    def register_library(self, core, name: str, spec: str):
        raise NotImplementedError

    def close_session(self, core) -> None:
        raise NotImplementedError

    def wire_stats(self) -> Dict[str, int]:
        """Bytes/frames this transport moved (framing included), plus the
        PR-9 data-plane counters: vectored write syscalls, shard-direct vs
        full-reassembly receive paths, and in-flight request depth."""
        return {
            "bytes_sent": 0,
            "bytes_received": 0,
            "frames": 0,
            "vectored_writes": 0,
            "shard_direct_receives": 0,
            "reassembly_receives": 0,
            "inflight": 0,
            "max_inflight": 0,
        }


class LoopbackTransport(Transport):
    """The in-process path, routed through the wire's array framing.

    Send and collect payloads are encoded to frame bytes and decoded back
    before touching the engine — a genuine serialization boundary with zero
    sockets — so every tier-1 test exercises the codec a TCP deployment
    uses, and the recorded frame bytes give the wire benchmark its loopback
    baseline. Control verbs dispatch directly: their codec coverage lives in
    the run task's ALPK round trip (client.py) and in the TCP transport.
    """

    name = "loopback"

    def __init__(self):
        self.bytes_framed = 0
        self.frames = 0
        self.counters: Dict[str, int] = {
            "shard_direct_receives": 0,
            "reassembly_receives": 0,
            "overlap_ns": 0,
            "put_ns": 0,
        }

    def _roundtrip(self, arr: np.ndarray) -> np.ndarray:
        header, chunks, framed = encode_array(arr)
        self.bytes_framed += framed
        self.frames += 1
        ftype, meta = unpack_frame(header)
        assert ftype == T_ARRAY
        # bytearray join keeps the decode zero-copy over this one buffer —
        # it IS the client array, not a reassembly staging copy.
        buf = bytearray()
        for c in chunks:
            buf += c
        return decode_array(meta, buf)

    def open_session(self, core, kwargs):
        return core.engine.connect(**kwargs)

    def _stage(self, core, arr: np.ndarray):
        """Shard-direct framing for the in-process path (DESIGN.md §13):
        encode with shard-aligned chunk boundaries and decode each chunk
        straight into a per-shard staging slab from the governor's pool —
        tier-1 exercises the same streaming decode TCP uses. Returns None
        when the layout has no row-slab geometry (cyclic/col-sharded/...)."""
        from repro.core.relayout import shard_geometry

        sess = getattr(core, "session", None)
        if sess is None:
            return None
        if core.engine_layout.cyclic:
            # Cyclic residency forbids pre-padding (the permutation would
            # interleave the zero rows) — staging slabs are padded, so keep
            # cyclic pipelines on the classic path and its loud failures.
            return None
        geom = shard_geometry(arr.shape, arr.dtype, core.client_layout, sess.mesh)
        if geom is None:
            return None
        # pads stay (0, 0): the stream is the *logical* bytes; the receive
        # side materializes pad slack in the slabs (a fallback decoder that
        # ignores __shards reassembles the logical array unchanged).
        header, chunks, framed = encode_array(arr, geom=geom)
        self.bytes_framed += framed
        self.frames += 1
        ftype, meta = unpack_frame(header)
        assert ftype == T_ARRAY
        mg = sess.memgov
        recv = ShardStreamReceiver(
            meta, geom, pool=mg.staging, ring=mg.transfer_ring(), eager=mg.unbudgeted()
        )
        try:
            for c in chunks:
                recv.feed(c)
            staged = recv.finish()
        except BaseException:
            recv.abort()
            raise
        staged.on_assembled = self._record_overlap
        return staged

    def _record_overlap(self, staged) -> None:
        ratio = staged.overlap_ratio()
        if ratio is None:
            return
        put = sum(e - s for s, e in staged.put_windows)
        self.counters["put_ns"] += int(put * 1e9)
        self.counters["overlap_ns"] += int(ratio * put * 1e9)

    def submit_send(self, core, array, *, name, block, key=None, payload=None):
        arr = np.asarray(array)
        staged = self._stage(core, arr)
        if staged is not None:
            self.counters["shard_direct_receives"] += 1
            return core._local_submit_send(
                staged, name=name, block=block, key=key, payload=payload
            )
        self.counters["reassembly_receives"] += 1
        arr = self._roundtrip(arr)
        return core._local_submit_send(arr, name=name, block=block, key=key, payload=payload)

    def submit_run(self, core, library, routine, args, params, *, block, out_shapes, out_dtype):
        # Direct dispatch: the run task itself drives every scalar through
        # the ALPK codec (see ClientCore._local_submit_run), preserving the
        # classic failure timing — unserializable args fail the future, not
        # the call site.
        return core._local_submit_run(
            library, routine, args, params,
            block=block, out_shapes=out_shapes, out_dtype=out_dtype,
        )

    def submit_collect(self, core, h):
        fut = core._local_submit_collect(h)
        return fut.then(lambda out: self._roundtrip(np.asarray(out)), label="collect:wire")

    def free(self, core, h):
        return core._local_free_async(h)

    def barrier(self, core, timeout):
        core.session.drain(timeout)

    def register_library(self, core, name, spec):
        return core._local_register_library(name, spec)

    def close_session(self, core):
        core.engine.release(core.session)

    def wire_stats(self):
        return {
            "bytes_sent": self.bytes_framed,
            "bytes_received": 0,
            "frames": self.frames,
            "vectored_writes": 0,  # no socket: nothing to coalesce
            "inflight": 0,
            "max_inflight": 0,
            **self.counters,
        }


def resolve_transport(spec: Any, default_env: str = "REPRO_TRANSPORT") -> Transport:
    """``None`` → the ``REPRO_TRANSPORT`` env default (``loopback``);
    a name → a fresh instance; a Transport instance → itself."""
    if spec is None:
        spec = os.environ.get(default_env, "loopback") or "loopback"
    if isinstance(spec, Transport):
        return spec
    if spec == "loopback":
        return LoopbackTransport()
    if spec == "tcp":
        from repro.serve.wire import TcpTransport

        return TcpTransport()
    raise SessionError(
        f"unknown transport {spec!r}; expected 'loopback', 'tcp', or a Transport instance"
    )
