"""The bridge: resharding matrices between layouts, with a transfer-cost model.

This module is the TPU adaptation of the paper's socket-transfer machinery
(§2.1 "The critical functionality of Alchemist is an efficient implementation
of communication for distributed data structures"). On Cori the bridge was
row-at-a-time TCP streams between Spark executors and MPI workers; on a TPU
mesh it is a single resharding boundary, lowered by XLA to
``all-to-all``/``collective-permute`` on ICI.

Two faces:

- :func:`relayout` / :func:`relayout_in_jit` — perform the resharding
  (eagerly via ``jax.device_put`` or inside a jitted program via
  ``with_sharding_constraint``).
- :func:`transfer_cost` — the analytic model of the same movement: exact
  bytes-that-change-owner and message counts per (src-device, dst-device)
  pair. This is what reproduces the *shape* of the paper's Tables 2–3
  (tall-skinny vs short-wide transfer behaviour) without a TCP wall clock:
  the row-granular wire format's cost reappears as message count and
  per-message size.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.core.errors import LayoutError
from repro.core.layouts import LayoutSpec, cyclic_permutation, inverse_permutation

#: ops.pad_to / ops.strip_to path names that mean "the fused Pallas kernel
#: actually ran" (vs the jnp reference fallback). Consumers — the plan cache,
#: SessionStats.fused_relayouts, the governor's refill — test membership here
#: rather than string-matching, so adding a backend stays a one-line change.
FUSED_PATHS = ("pallas", "pallas-interpret")


def _kernel_ops():
    """Lazy kernels.ops import: relayout is imported by modules that must not
    pay the Pallas import (and kernels.ops probes the backend at import)."""
    from repro.kernels import ops as kops

    return kops


# ---------------------------------------------------------------------------
# Shard-interval geometry
# ---------------------------------------------------------------------------

def shard_intervals(n: int, n_shards: int) -> np.ndarray:
    """[n_shards, 2] (start, end) intervals of a block decomposition.

    XLA pads uneven dims: shard size is ceil(n / n_shards); trailing shards
    may be empty. end is clamped to n.
    """
    size = -(-n // n_shards)
    starts = np.arange(n_shards) * size
    ends = np.minimum(starts + size, n)
    starts = np.minimum(starts, n)
    return np.stack([starts, ends], axis=1)


@dataclasses.dataclass(frozen=True)
class ShardGeometry:
    """Row-slab decomposition a shard-direct wire transfer targets (§13).

    Describes how a 2D matrix staged under a pure row layout decomposes into
    per-device slabs: the wire can then align its chunk boundaries with the
    slabs, and a receiver can decode each slab straight into its own staging
    buffer and ``device_put`` it as the bytes land — no full-array reassembly.
    The wire carries *logical* bytes only; the receiver zero-fills each slab's
    divisibility-pad slack, which is where the pad "kernel" of the legacy path
    goes in this path (fused into the decode).
    """

    shape: Tuple[int, int]  # logical (rows, cols)
    physical_shape: Tuple[int, int]  # rows padded to a shard-count multiple
    dtype: str
    n_shards: int
    shard_rows: int  # physical rows per slab (physical_shape[0] / n_shards)
    #: logical (start, end) row interval each shard carries on the wire;
    #: trailing shards of a short matrix may be empty.
    intervals: Tuple[Tuple[int, int], ...]
    layout_name: str
    mesh_key: Tuple
    #: shard index -> the jax.Device owning that slab under the layout.
    devices: Tuple[Any, ...]

    @property
    def pads(self) -> Tuple[int, int]:
        return (self.physical_shape[0] - self.shape[0], 0)

    @property
    def itemsize(self) -> int:
        return int(np.dtype(self.dtype).itemsize)

    def slab_shape(self) -> Tuple[int, int]:
        return (self.shard_rows, self.shape[1])

    def logical_bytes(self, shard: int) -> int:
        s, e = self.intervals[shard]
        return (e - s) * self.shape[1] * self.itemsize

    def matches(self, layout: LayoutSpec, mesh: Mesh) -> bool:
        return self.layout_name == layout.name and self.mesh_key == _mesh_cache_key(mesh)


def shard_geometry(
    shape: Tuple[int, int], dtype, layout: LayoutSpec, mesh: Mesh
) -> Optional[ShardGeometry]:
    """The :class:`ShardGeometry` for staging ``shape`` under ``layout``, or
    None when the layout cannot take a shard-direct stream: cyclic layouts
    (rows are stored permuted), column-sharded or replicated layouts (a slab
    is not a contiguous byte range of the logical array), empty matrices, and
    dtypes jax would silently canonicalize away (an f64 payload under default
    x64-off must take the reassembly path, whose ``jnp.asarray`` converts)."""
    if layout.cyclic:
        return None
    rows, cols = int(shape[0]), int(shape[1])
    if rows <= 0 or cols <= 0:
        return None
    dt = np.dtype(dtype)
    try:
        if jax.dtypes.canonicalize_dtype(dt) != dt:
            return None
    except Exception:  # pragma: no cover - exotic dtypes: fall back
        return None
    n_r, n_c = layout.grid_shape(mesh)
    n_dev = int(np.asarray(mesh.devices).size)
    if n_c != 1 or n_r != n_dev:
        return None  # column shards or replication: slabs are not row slabs
    pr, _pc = pad_amounts((rows, cols), layout, mesh)
    phys = (rows + pr, cols)
    shard_rows = phys[0] // n_r
    sharding = layout.sharding(mesh)
    try:
        imap = sharding.addressable_devices_indices_map(phys)
    except Exception:  # pragma: no cover - non-addressable meshes
        return None
    by_start: Dict[int, Any] = {}
    for dev, idx in imap.items():
        r = idx[0]
        by_start[0 if r.start is None else int(r.start)] = dev
    devices = []
    for j in range(n_r):
        dev = by_start.get(j * shard_rows)
        if dev is None:
            return None
        devices.append(dev)
    return ShardGeometry(
        shape=(rows, cols),
        physical_shape=phys,
        dtype=dt.name,
        n_shards=n_r,
        shard_rows=shard_rows,
        intervals=tuple((int(s), int(e)) for s, e in shard_intervals(rows, n_r)),
        layout_name=layout.name,
        mesh_key=_mesh_cache_key(mesh),
        devices=tuple(devices),
    )


def staged_pad_path(pads: Tuple[int, int]) -> str:
    """Accounting parity for shard-direct receives: the divisibility pad is
    fused into the staged decode itself (slack rows are memset in the slab,
    no separate pad op ever runs), so report the path the kernel dispatch
    *would* have taken — ``SessionStats.fused_relayouts`` keeps one meaning
    across the legacy and staged send paths."""
    if pads == (0, 0):
        return "none"
    kops = _kernel_ops()
    return kops._BACKEND if kops.use_pallas() else "ref"


def _device_shard_coords(layout: LayoutSpec, mesh: Mesh) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """For each device (flat order of mesh.devices): its (row-shard, col-shard)
    index under ``layout``, plus the grid shape (n_row_shards, n_col_shards)."""
    axis_names = list(mesh.axis_names)
    shape = mesh.devices.shape
    coords = np.indices(shape).reshape(len(shape), -1)  # [n_axes, n_dev]

    def shard_index(axes: Tuple[str, ...]) -> Tuple[np.ndarray, int]:
        idx = np.zeros(coords.shape[1], dtype=np.int64)
        total = 1
        for a in axes:
            if a not in axis_names:
                continue
            ai = axis_names.index(a)
            idx = idx * shape[ai] + coords[ai]
            total *= shape[ai]
        return idx, total

    row_idx, n_row = shard_index(layout.row_axes)
    col_idx, n_col = shard_index(layout.col_axes)
    return row_idx, col_idx, n_row, n_col


@dataclasses.dataclass(frozen=True)
class TransferCost:
    """Analytic cost of one relayout.

    Attributes:
      bytes_total: size of the matrix.
      bytes_moved: bytes that change device ownership (the ICI traffic).
      messages: number of (src device, dst device) pairs exchanging data.
      max_message_bytes / min_message_bytes: extremes over messages.
      row_fragments: number of distinct (row-slab x device-pair) fragments —
        the analogue of the paper's per-row sends; high counts are the
        tall-skinny penalty of Tables 2–3.
      replication_factor: dst copies per element (replicated layouts).
    """

    bytes_total: int
    bytes_moved: int
    messages: int
    max_message_bytes: int
    min_message_bytes: int
    row_fragments: int
    replication_factor: float

    @property
    def moved_fraction(self) -> float:
        return self.bytes_moved / max(self.bytes_total, 1)

    def ici_seconds(self, link_bw: float = 50e9, n_links: Optional[int] = None) -> float:
        """Lower-bound transfer time at ``link_bw`` bytes/s per device link."""
        links = n_links or 1
        return self.bytes_moved / (link_bw * links)


def transfer_cost(
    shape: Tuple[int, int],
    dtype,
    src: LayoutSpec,
    dst: LayoutSpec,
    mesh: Mesh,
) -> TransferCost:
    """Exact bytes/messages for a src→dst relayout of ``shape`` on ``mesh``.

    Model: under ``src`` each device owns a (row-interval x col-interval)
    block (devices sharing a shard index hold replicas; we count the src copy
    in the same mesh position as the canonical owner and charge replication
    on the destination side, which matches how XLA lowers broadcast-like
    resharding as all-gathers).
    """
    n_rows, n_cols = int(shape[0]), int(shape[1])
    itemsize = jnp.dtype(dtype).itemsize
    bytes_total = n_rows * n_cols * itemsize

    s_row_idx, s_col_idx, s_nr, s_nc = _device_shard_coords(src, mesh)
    d_row_idx, d_col_idx, d_nr, d_nc = _device_shard_coords(dst, mesh)

    s_rows = shard_intervals(n_rows, s_nr)
    s_cols = shard_intervals(n_cols, s_nc)
    d_rows = shard_intervals(n_rows, d_nr)
    d_cols = shard_intervals(n_cols, d_nc)

    n_dev = s_row_idx.shape[0]
    # Canonical source owner per src shard (first device holding that shard):
    # replicas don't re-send.
    owner = {}
    src_owner = np.zeros(n_dev, dtype=bool)
    for dev in range(n_dev):
        key = (int(s_row_idx[dev]), int(s_col_idx[dev]))
        if key not in owner:
            owner[key] = dev
            src_owner[dev] = True

    # Per-device intervals.
    sr = s_rows[s_row_idx]  # [n_dev, 2]
    sc = s_cols[s_col_idx]
    dr = d_rows[d_row_idx]
    dc = d_cols[d_col_idx]

    # Pairwise overlaps, vectorized: overlap length of [a0,a1) x [b0,b1).
    def overlap(a: np.ndarray, b: np.ndarray) -> np.ndarray:
        lo = np.maximum(a[:, None, 0], b[None, :, 0])
        hi = np.minimum(a[:, None, 1], b[None, :, 1])
        return np.maximum(hi - lo, 0)

    row_ov = overlap(sr, dr)  # [src_dev, dst_dev]
    col_ov = overlap(sc, dc)
    elems = row_ov.astype(np.int64) * col_ov.astype(np.int64)
    elems[~src_owner, :] = 0  # replicas don't send
    np.fill_diagonal(elems, 0)  # data already in place is free

    msg_bytes = elems * itemsize
    nonzero = msg_bytes > 0
    bytes_moved = int(msg_bytes.sum())
    messages = int(nonzero.sum())
    max_msg = int(msg_bytes.max()) if messages else 0
    min_msg = int(msg_bytes[nonzero].min()) if messages else 0
    # Row fragments: each message carries row_ov distinct row slices (the
    # paper streamed each row separately; fragment count is the TCP-message
    # analogue).
    row_frag = int((row_ov * nonzero).sum())

    dst_copies = n_dev / (d_nr * d_nc)
    return TransferCost(
        bytes_total=bytes_total,
        bytes_moved=bytes_moved,
        messages=messages,
        max_message_bytes=max_msg,
        min_message_bytes=min_msg,
        row_fragments=row_frag,
        replication_factor=float(dst_copies),
    )


# ---------------------------------------------------------------------------
# Pad-to-divisible geometry
# ---------------------------------------------------------------------------
#
# ``jax.device_put`` into a NamedSharding (the bridge's send path) requires
# each sharded dim to be divisible by its shard count, so e.g. a
# 6x6 matrix could not be sent to a 4-worker session. The bridge lifts this by
# padding each dim up to the next multiple of its destination shard count with
# zero rows/cols before ``device_put`` and slicing the padding back off on
# collect/refill. Padding amounts are part of the relayout plan; the handle
# layer records (pad_rows, pad_cols) so logical reads never see the zeros.


def pad_amounts(shape: Tuple[int, int], dst: LayoutSpec, mesh: Mesh) -> Tuple[int, int]:
    """(pad_rows, pad_cols) making ``shape`` shardable under ``dst`` on ``mesh``.

    Cyclic layouts cannot be padded: the emulation's row permutation is a
    function of the physical length, so appended zero rows would interleave
    into the interior and silently corrupt ``data()``/collect slicing. An
    uneven shape into a cyclic layout raises loudly instead (exactly the
    pre-padding behaviour of the bare ``device_put``).
    """
    n_r, n_c = dst.grid_shape(mesh)
    pads = (-int(shape[0])) % n_r, (-int(shape[1])) % n_c
    if pads != (0, 0) and dst.cyclic:
        raise LayoutError(
            f"shape {tuple(shape)} is not divisible for cyclic layout {dst.name!r} "
            f"(grid {n_r}x{n_c}); pad-to-divisible does not compose with the "
            "cyclic row permutation — pad the matrix explicitly before sending"
        )
    return pads


def pad_for(
    x: jax.Array, dst: LayoutSpec, mesh: Mesh
) -> Tuple[jax.Array, Tuple[int, int], str]:
    """Zero-pad ``x`` so ``device_put`` into ``dst`` is legal.

    Returns ``(padded, pads, path)`` where ``path`` names the kernel backend
    that performed the pad ("pallas"/"pallas-interpret"/"ref", see
    :data:`FUSED_PATHS`) or "none" when no padding was needed.
    """
    pads = pad_amounts(tuple(x.shape), dst, mesh)
    path = "none"
    if pads != (0, 0):
        m, n = int(x.shape[0]), int(x.shape[1])
        x, path = _kernel_ops().pad_to(x, (m + pads[0], n + pads[1]))
    return x, pads, path


# ---------------------------------------------------------------------------
# Performing the relayout
# ---------------------------------------------------------------------------

def relayout(
    x: jax.Array,
    dst: LayoutSpec,
    mesh: Mesh,
    *,
    src: Optional[LayoutSpec] = None,
    donate: bool = False,
) -> jax.Array:
    """Eagerly reshard ``x`` (a 2D matrix) into layout ``dst`` on ``mesh``.

    If the source layout was cyclic and the destination is not (or vice
    versa), the row permutation is applied/undone first. Shapes whose dims
    are not divisible by the destination shard counts are padded for the
    ``device_put`` and sliced back, so the logical shape is preserved.
    """
    dst.validate(x.shape, mesh)
    arr = x
    src_cyclic = bool(src.cyclic) if src is not None else False
    if src_cyclic != dst.cyclic:
        if dst.cyclic:
            n_shards = dst.grid_shape(mesh)[0]
        else:
            n_shards = src.grid_shape(mesh)[0] if src else 1
        perm = cyclic_permutation(x.shape[0], n_shards)
        if dst.cyclic:
            arr = jnp.take(arr, jnp.asarray(perm), axis=0)
        else:
            arr = jnp.take(arr, jnp.asarray(inverse_permutation(perm)), axis=0)
    arr, pads, _ = pad_for(arr, dst, mesh)
    out = jax.device_put(arr, dst.sharding(mesh), donate=donate)
    if pads != (0, 0):
        out, _ = _kernel_ops().strip_to(out, (x.shape[0], x.shape[1]))
    return out


def relayout_in_jit(x: jax.Array, dst: LayoutSpec, mesh: Mesh) -> jax.Array:
    """Resharding boundary usable inside a jitted program."""
    return jax.lax.with_sharding_constraint(x, dst.sharding(mesh))


# ---------------------------------------------------------------------------
# Relayout plan cache
# ---------------------------------------------------------------------------

def _mesh_cache_key(mesh: Mesh) -> Tuple:
    """Hashable identity of a mesh: axis names, grid shape, device ids."""
    devices = np.asarray(mesh.devices, dtype=object).ravel()
    return (
        tuple(mesh.axis_names),
        tuple(np.asarray(mesh.devices).shape),
        tuple(getattr(d, "id", i) for i, d in enumerate(devices)),
    )


@dataclasses.dataclass
class RelayoutPlan:
    """Everything derivable from (shape, dtype, src, dst, mesh) alone.

    Building a plan is the expensive, data-independent half of a transfer:
    the O(n_devices^2) shard-geometry sweep of :func:`transfer_cost`, the
    cyclic row permutation (an O(n_rows) host-side index build shipped to
    device), and the destination NamedSharding. A cached plan turns a repeat
    send/collect of the same (shape, dtype, layout pair, mesh) into a single
    ``device_put`` — the paper's "minimal communication overhead" claim made
    structural (DESIGN.md §5).
    """

    shape: Tuple[int, int]
    dtype: Any
    src_name: str
    dst_name: str
    cost: TransferCost
    dst_sharding: NamedSharding
    permutation: Optional[jnp.ndarray]  # pre-relayout row permutation, if any
    pads: Tuple[int, int] = (0, 0)  # zero rows/cols appended for divisibility
    uses: int = 0
    #: Kernel backend that ran this plan's last pad or strip — a member of
    #: :data:`FUSED_PATHS` when the fused Pallas kernel compiled, "ref" for
    #: the jnp fallback, None for unpadded plans. Last-write-wins across
    #: threads is fine: the plan's geometry is fixed, so every apply of the
    #: same plan takes the same path (the backend probe is module-static).
    fused_path: Optional[str] = None

    @property
    def physical_shape(self) -> Tuple[int, int]:
        return (self.shape[0] + self.pads[0], self.shape[1] + self.pads[1])

    def apply(self, x: jax.Array, *, donate: bool = False) -> jax.Array:
        """Execute the planned relayout on ``x`` (async-dispatched).

        Returns the *physical* (possibly padded) array; use :meth:`strip` to
        recover the logical matrix, or keep it padded for residency and strip
        on read (the handle layer's choice). With ``donate=True`` the input
        buffer is donated to the ``device_put`` (the governor's refill path:
        its host staging copy is dead after the put).
        """
        arr = x
        if self.permutation is not None:
            arr = jnp.take(arr, self.permutation, axis=0)
        if self.pads != (0, 0):
            arr, self.fused_path = _kernel_ops().pad_to(arr, self.physical_shape)
            # the pad kernel's output is ours alone — always safe to donate
            donate = True
        return jax.device_put(arr, self.dst_sharding, donate=donate)

    def strip(self, y: jax.Array) -> jax.Array:
        """Slice the divisibility padding back off a planned-relayout result."""
        if self.pads == (0, 0):
            return y
        out, self.fused_path = _kernel_ops().strip_to(y, self.shape)
        return out


class RelayoutPlanCache:
    """Per-session memo of :class:`RelayoutPlan`, keyed on
    ``(shape, dtype, src_layout, dst_layout, mesh)``.

    Thread-safe; hit/miss counters feed ``session.stats``.
    """

    def __init__(self):
        self._plans: Dict[Tuple, RelayoutPlan] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._plans)

    def plan(
        self,
        shape: Tuple[int, int],
        dtype,
        src: LayoutSpec,
        dst: LayoutSpec,
        mesh: Mesh,
    ) -> Tuple[RelayoutPlan, bool]:
        """Return ``(plan, was_cache_hit)`` for this relayout geometry."""
        key = (
            tuple(int(d) for d in shape),
            str(jnp.dtype(dtype)),
            src.name,
            dst.name,
            _mesh_cache_key(mesh),
        )
        with self._lock:
            cached = self._plans.get(key)
            if cached is not None:
                self.hits += 1
                cached.uses += 1
                return cached, True
            self.misses += 1
        # Build outside the lock: geometry sweeps can be slow and plans are
        # deterministic, so a racing double-build is harmless.
        built = self._build(shape, dtype, src, dst, mesh)
        with self._lock:
            plan = self._plans.setdefault(key, built)
            plan.uses += 1
        return plan, False

    @staticmethod
    def _build(shape, dtype, src: LayoutSpec, dst: LayoutSpec, mesh: Mesh) -> RelayoutPlan:
        dst.validate(shape, mesh)
        cost = transfer_cost(tuple(shape), dtype, src, dst, mesh)
        perm = None
        if bool(src.cyclic) != bool(dst.cyclic):
            n_shards = dst.grid_shape(mesh)[0] if dst.cyclic else src.grid_shape(mesh)[0]
            p = cyclic_permutation(shape[0], n_shards)
            if not dst.cyclic:
                p = inverse_permutation(p)
            perm = jnp.asarray(p)
        return RelayoutPlan(
            shape=tuple(shape),
            dtype=jnp.dtype(dtype),
            src_name=src.name,
            dst_name=dst.name,
            cost=cost,
            dst_sharding=dst.sharding(mesh),
            permutation=perm,
            pads=pad_amounts(tuple(shape), dst, mesh),
        )

    def stats(self) -> Dict[str, int]:
        with self._lock:
            fused = sum(1 for p in self._plans.values() if p.fused_path in FUSED_PATHS)
            return {
                "hits": self.hits,
                "misses": self.misses,
                "plans": len(self._plans),
                "fused_plans": fused,
            }


@dataclasses.dataclass
class TransferRecord:
    """One observed transfer: analytic cost + measured wall time."""

    direction: str  # "send" (client→engine) or "receive" (engine→client)
    cost: TransferCost
    seconds: float
    cache_hit: bool = False  # did the relayout plan come from the plan cache?
    pads: Tuple[int, int] = (0, 0)  # divisibility padding applied by the plan
    #: False for transfers that never consulted the plan cache (a collect
    #: served from the governor's host store) — they must not count toward
    #: the cache hit/miss rate.
    planned: bool = True
    #: Did the fused Pallas pad/strip kernel run for this transfer (vs the
    #: jnp reference or no padding at all)? Feeds SessionStats.fused_relayouts.
    fused: bool = False


def timed_relayout(
    x: jax.Array,
    dst: LayoutSpec,
    mesh: Mesh,
    *,
    src: LayoutSpec,
    direction: str = "send",
    cache: Optional[RelayoutPlanCache] = None,
    block: bool = True,
    strip: bool = True,
) -> Tuple[jax.Array, TransferRecord]:
    """Relayout + analytic cost + measured wall time, as one record.

    This is the engine's instrumented transfer path: the paper reports
    Send/Compute/Receive columns (Table 1); records produced here feed the
    same decomposition.

    With ``cache`` the shard geometry / permutation / sharding come from the
    session's :class:`RelayoutPlanCache`. With ``block=False`` the relayout is
    dispatched asynchronously and ``seconds`` measures dispatch only — the
    task-queue engine's pipelined path, where the wait is absorbed by the
    eventual ``collect``. With ``strip=False`` a divisibility-padded result is
    returned physical (padded); the caller records ``rec.pads`` against the
    handle so logical reads slice the zeros back off (the send path's choice
    — a resident matrix keeps its put-legal physical form for cheap refills).
    """
    with jax.profiler.TraceAnnotation("al.relayout", direction=direction, nbytes=x.nbytes):
        hit = False
        pads = (0, 0)
        fused = False
        if cache is not None:
            plan, hit = cache.plan(tuple(x.shape), x.dtype, src, dst, mesh)
            cost = plan.cost
            pads = plan.pads
            t0 = time.perf_counter()
            out = plan.apply(x)
            if strip:
                out = plan.strip(out)
                pads = (0, 0)
            fused = plan.fused_path in FUSED_PATHS
        else:
            cost = transfer_cost(tuple(x.shape), x.dtype, src, dst, mesh)
            t0 = time.perf_counter()
            out = relayout(x, dst, mesh, src=src)  # pads + strips internally
        if block:
            out.block_until_ready()
        dt = time.perf_counter() - t0
        return out, TransferRecord(
            direction=direction, cost=cost, seconds=dt, cache_hit=hit, pads=pads, fused=fused
        )
