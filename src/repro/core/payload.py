"""Host payloads of the resident store (DESIGN.md §8, §13).

A store entry's payload is the matrix's logical host bytes: an ndarray (the
planner's snapshot, a fetched copy) or a :class:`SlabPayload`, the received
staging slabs of a shard-direct send taken over as they lie. This module
owns the slab payload's lifetime — the slabs go back to the governor's
staging pool when the last store entry holding them dies — and the two ways
a payload reaches the devices again (:func:`place`, :func:`to_default_device`),
which read a slab payload slab by slab instead of joining it on the host.

On a CPU backend a ``device_put`` of an aligned host buffer is zero-copy:
the placed array's backing store IS the buffer (:func:`aliases_host`). A
slab that a device array aliases is pinned and never pooled, so a later
receive cannot write into a live matrix.
"""

from __future__ import annotations

import threading
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.relayout import FUSED_PATHS, RelayoutPlan


def aliases_host(arr: jax.Array, host: np.ndarray) -> bool:
    """True if any device shard of ``arr`` shares memory with ``host``. On CPU
    backends a sharded/donated ``device_put`` of a numpy array is zero-copy —
    the placed array's backing store IS the host buffer — so a staging buffer
    aliased by a live device array must never return to the pool: a later
    spill's gather would write the victim's bytes straight through the alias
    into the resident matrix."""
    try:
        base = host.ctypes.data
        end = base + host.nbytes
        for shard in arr.addressable_shards:
            ptr = shard.data.unsafe_buffer_pointer()
            if base <= ptr < end:
                return True
        return False
    except Exception:  # pragma: no cover - exotic runtimes: assume aliased
        return True


def join_rows(slabs, shape, dtype, want=None, copy=None) -> np.ndarray:
    """Row slabs as one (rows, cols) array: the slab itself when there is
    one and no copy is asked for, else a concatenation (the one full copy)."""
    if len(slabs) == 1:
        full = slabs[0].copy() if copy else slabs[0]
    else:
        parts = [s for s in slabs if s.size] or [np.empty((0, shape[1]), dtype)]
        full = np.concatenate(parts, axis=0)
    full = full.reshape(shape)
    return full.astype(want, copy=False) if want is not None else full


class SlabPayload:
    """A resident-store payload adopted from a shard-direct receive: the
    logical matrix as the received per-shard slabs, read-only, with no
    reassembly copy.

    Quacks like the logical ndarray for the store's readers (``shape``,
    ``dtype``, ``nbytes``, ``slabs`` with their rows, and ``__array__``,
    which is the slab itself when there is one shard and a full copy only
    when there are several — :func:`place` and :func:`to_default_device`
    never need it). Each store entry holding it retains it once; when the
    last lets go (``release``: its last placement freed, or its entry
    evicted or cleared) the slabs go back to the staging pool, except those
    a device array aliases (``pin_aliases``), which are left to the garbage
    collector.
    """

    def __init__(self, bases, slabs, shape, dtype, pool):
        self._bases = bases  # physical slabs, pad slack included
        self.slabs = []
        for slab in slabs:
            view = slab.view()
            view.flags.writeable = False
            self.slabs.append(view)
        self.shape = tuple(int(d) for d in shape)
        self.dtype = np.dtype(dtype)
        self._pool = pool
        self._pinned: set = set()
        self._holders = 1
        self._lock = threading.Lock()

    @property
    def nbytes(self) -> int:
        return self.shape[0] * self.shape[1] * self.dtype.itemsize

    def __array__(self, dtype=None, copy=None):
        return join_rows(self.slabs, self.shape, self.dtype, dtype, copy)

    def block(self, index) -> np.ndarray:
        """Block ``index`` (a pair of slices) of the matrix: a view of the one
        slab that holds its rows, else joined from the slabs it straddles."""
        r0, r1, _ = index[0].indices(self.shape[0])
        pieces = []
        at = 0
        for slab in self.slabs:
            lo, hi = max(r0, at), min(r1, at + slab.shape[0])
            if lo < hi:
                pieces.append(slab[lo - at : hi - at, index[1]])
            at += slab.shape[0]
        return pieces[0] if len(pieces) == 1 else np.concatenate(pieces, axis=0)

    def joined_on_device(self) -> jax.Array:
        """The matrix on the default device: each slab put on its own and the
        slabs joined there, not on the host. Slabs a put aliases are pinned."""
        parts = [jnp.asarray(s) for s in self.slabs if s.size]
        self.pin_aliases(*parts)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)

    def pin_aliases(self, *arrays) -> None:
        """Never pool a slab that one of ``arrays`` shares memory with."""
        live = [a for a in arrays if a is not None]
        with self._lock:
            for j, base in enumerate(self._bases):
                if any(aliases_host(a, base) for a in live):
                    self._pinned.add(j)

    def retain(self) -> None:
        with self._lock:
            self._holders += 1

    def release(self) -> None:
        with self._lock:
            self._holders -= 1
            if self._holders or self._pool is None:
                return
            bases = [b for j, b in enumerate(self._bases) if j not in self._pinned]
            self._bases = []
        for base in bases:
            self._pool.release(base)


def place(payload, plan: RelayoutPlan, *, donate: bool = False) -> Tuple[jax.Array, bool]:
    """A store payload on devices by a pure placement ``plan`` (source and
    destination layout alike: pads, no permutation) → (physical array,
    whether the fused pad kernel ran). Slabs the placed array aliases are
    pinned.

    A payload of several slabs never joins them on the host. Unpadded, it
    goes block by block (``jax.make_array_from_callback``): each device's
    block is read from the slab that holds its rows, and only a block that
    straddles slabs is assembled. Padded, it is joined on the device and
    padded there by the fused kernel, as an ndarray payload is."""
    if isinstance(payload, SlabPayload) and len(payload.slabs) > 1:
        if plan.pads == (0, 0) and plan.permutation is None:
            out = jax.make_array_from_callback(plan.shape, plan.dst_sharding, payload.block)
            payload.pin_aliases(out)
            return out, False
        x = payload.joined_on_device()
    else:
        x = np.asarray(payload, dtype=jax.dtypes.canonicalize_dtype(payload.dtype))
    out = plan.apply(x, donate=donate)
    if isinstance(payload, SlabPayload):
        payload.pin_aliases(out)
    return out, plan.fused_path in FUSED_PATHS


def to_default_device(payload, rows: int, cols: int) -> jax.Array:
    """The leading ``rows`` × ``cols`` of a host payload on the default
    device — a collect served from host memory. A slab payload (logical, so
    already that shape) is joined on the device, and the slabs its puts
    alias are pinned, since the result is served to a client."""
    if isinstance(payload, SlabPayload):
        return payload.joined_on_device()
    return jnp.asarray(np.asarray(payload)[:rows, :cols])
