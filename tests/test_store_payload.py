"""The resident store's key and payload without fresh host copies (DESIGN.md
§8, §13): ``content_key`` hashes the bytes in place and agrees with the
staged receive's streaming key, and a TCP send's received staging slabs
become the store entry's payload, uncopied, going back to the staging pool
only when the entry dies — while every reader of a payload (attach,
migration and refill, the governor's free spill, fleet replay) still gets
the bytes bit for bit. The multi-shard case runs on a host-device mesh in
tests/multidevice/_adoption_script.py."""

import hashlib
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.expr import content_key
from repro.core.handles import SPILLED
from repro.core.relayout import ShardGeometry
from repro.core.payload import SlabPayload
from repro.core.transport import StagedShards

ELEMENTAL = "repro.linalg.library:ElementalLib"


# ---------------------------------------------------------------------------
# content_key: in place, and the same triple as before
# ---------------------------------------------------------------------------


def _staged(arr: np.ndarray, n_shards: int = 3) -> StagedShards:
    """``arr``'s rows as a shard-direct receive lays them down: one padded
    slab per shard, the last ones possibly short or empty."""
    rows, cols = arr.shape
    shard_rows = max(1, -(-rows // n_shards))
    intervals = tuple(
        (min(j * shard_rows, rows), min((j + 1) * shard_rows, rows)) for j in range(n_shards)
    )
    geom = ShardGeometry(
        shape=(rows, cols),
        physical_shape=(shard_rows * n_shards, cols),
        dtype=np.dtype(arr.dtype).name,
        n_shards=n_shards,
        shard_rows=shard_rows,
        intervals=intervals,
        layout_name="row",
        mesh_key=(),
        devices=(None,) * n_shards,
    )
    buffers = []
    for s, e in intervals:
        buf = np.zeros((shard_rows, cols), arr.dtype)
        buf[: e - s] = arr[s:e]
        buffers.append(buf)
    return StagedShards(geom, buffers)


def _layouts(dtype):
    rng = np.random.default_rng(7)
    base = rng.standard_normal((12, 7)).astype(dtype)
    return {
        "c_order": base,
        "f_order": np.asfortranarray(base),
        "strided": rng.standard_normal((24, 21)).astype(dtype)[::2, ::3],
        "zero_rows": np.empty((0, 7), dtype),
    }


@pytest.mark.parametrize("layout", ["c_order", "f_order", "strided", "zero_rows"])
@pytest.mark.parametrize(
    "dtype", [np.float32, np.float64, jnp.bfloat16], ids=["f32", "f64", "bf16"]
)
def test_content_key_matches_the_copying_digest_and_the_staged_key(layout, dtype):
    arr = _layouts(dtype)[layout]
    want = (
        tuple(arr.shape),
        str(arr.dtype),
        hashlib.sha1(np.ascontiguousarray(arr).tobytes()).hexdigest(),
    )
    assert content_key(arr) == want
    staged = _staged(arr)
    assert staged.content_key() == want
    assert content_key(staged) == want


def test_content_key_of_a_contiguous_array_makes_no_copy():
    a = np.ones((4096, 4096), np.float32)  # 64 MiB
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        content_key(a)
        _current, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 1 << 20


# ---------------------------------------------------------------------------
# payload adoption over TCP
# ---------------------------------------------------------------------------


@pytest.fixture()
def engine():
    return repro.AlchemistEngine()


def _tcp(engine, **kw):
    # One worker, so one slab, on a host of any device count.
    s = repro.connect(engine, transport="tcp", workers=1, **kw)
    s.register_library("elemental", ELEMENTAL)
    return s


def _slab_at(shape, dtype, offset: int) -> np.ndarray:
    """A fresh slab whose address is ``offset`` past a 64-byte boundary. A
    CPU ``device_put`` aliases a 64-byte-aligned host buffer instead of
    copying it (a TPU never does), so the offset decides which of the two
    recycling rules a test meets."""
    n = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.empty(n + 128, np.uint8)
    start = (offset - raw.ctypes.data) % 64
    return raw[start : start + n].view(dtype).reshape(shape)


def _record_slabs(engine, offset: int = 16) -> list:
    """Every staging slab the engine's pool hands out from now on; fresh
    ones at ``offset`` past a 64-byte boundary (see :func:`_slab_at`)."""
    pool = engine.memgov.staging
    handed = []
    acquire = pool.acquire

    def recording(shape, dtype):
        if any(b.shape == tuple(shape) and b.dtype == dtype for b in pool._free):
            buf = acquire(shape, dtype)
        else:
            buf = _slab_at(shape, dtype, offset)
        handed.append(buf)
        return buf

    pool.acquire = recording
    return handed


def _mat(seed, shape=(64, 48)):
    return np.random.default_rng(seed).standard_normal(shape).astype(np.float32)


def _entry(engine, arr):
    entry = engine.residents.lookup(content_key(arr))
    assert entry is not None
    return entry


class TestPayloadAdoption:
    def test_payload_is_the_received_slab(self, engine):
        handed = _record_slabs(engine)
        s = _tcp(engine)
        a = _mat(0)
        s.send(a).materialize()
        (slab,) = handed
        payload = _entry(engine, a).payload
        assert isinstance(payload, SlabPayload)
        view = np.asarray(payload)
        assert np.shares_memory(view, slab)
        assert not view.flags.writeable  # nothing may write under the entry
        np.testing.assert_array_equal(view, a)
        stats = engine.residents.stats()
        assert stats["payload_adopted_bytes"] == a.nbytes
        assert stats["payload_copied_bytes"] == 0
        s.close()

    def test_live_payload_survives_the_next_receive(self, engine):
        handed = _record_slabs(engine)
        s = _tcp(engine)
        a, b = _mat(1), _mat(2)
        s.send(a).materialize()
        s.send(b).materialize()
        assert handed[0] is not handed[1]  # a's slab never went back to the pool
        np.testing.assert_array_equal(np.asarray(_entry(engine, a).payload), a)
        np.testing.assert_array_equal(np.asarray(_entry(engine, b).payload), b)
        s.close()

    def test_free_returns_the_slab_for_the_next_receive(self, engine):
        handed = _record_slabs(engine)
        pool = engine.memgov.staging
        s = _tcp(engine)
        a, c = _mat(3), _mat(4)
        la = s.send(a)
        la.materialize()
        la.free()
        assert engine.residents.lookup(content_key(a)) is None
        assert any(buf is handed[0] for buf in pool._free)
        reuses = pool.reuses
        lc = s.send(c)
        np.testing.assert_array_equal(np.asarray(lc.data()), c)
        assert pool.reuses == reuses + 1 and handed[1] is handed[0]
        np.testing.assert_array_equal(np.asarray(_entry(engine, c).payload), c)
        s.close()

    def test_slab_a_device_array_aliases_never_returns(self, engine):
        from repro.core.payload import aliases_host

        handed = _record_slabs(engine, offset=0)  # aligned: CPU puts alias
        s = _tcp(engine)
        a = _mat(10)
        la = s.send(a)
        aliased = aliases_host(s.session.resolve(la.materialize()).data(), handed[0])
        la.free()
        returned = any(buf is handed[0] for buf in engine.memgov.staging._free)
        assert returned is not aliased
        s.close()

    def test_plain_sends_keep_no_payload(self, engine):
        # A send outside the planner asks for no payload: the slabs go back
        # to the pool once the device array is assembled.
        handed = _record_slabs(engine)
        ac = repro.AlchemistContext(engine, num_workers=1, transport="tcp")
        a = _mat(5)
        ac.send(a)
        ac.wait()
        assert _entry(engine, a).payload is None
        assert any(buf is handed[0] for buf in engine.memgov.staging._free)
        assert engine.residents.stats()["payload_adopted_bytes"] == 0
        ac.stop()


def test_slab_payload_holders_survive_racing_retains_and_releases():
    """Stores on many threads retain and release one payload (fleet
    ``adopt`` against entry deaths): its slabs reach the pool exactly when
    the last holder lets go, never before."""
    import sys
    import threading

    from repro.core.memgov import _StagingPool

    pool = _StagingPool(max_buffers=8)
    bases = [np.zeros((4, 3), np.float32) for _ in range(3)]
    payload = SlabPayload(bases, [b[:4] for b in bases], (12, 3), np.float32, pool)
    start = threading.Barrier(16)

    def holder():
        start.wait(10)
        for _ in range(200):
            payload.retain()
            payload.release()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=holder) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert pool._free == []  # the first holder still holds
    payload.release()
    assert {id(b) for b in pool._free} == {id(b) for b in bases}


# ---------------------------------------------------------------------------
# slab-backed payloads still serve their readers bit for bit
# ---------------------------------------------------------------------------


class TestSlabPayloadReaders:
    def test_migration_on_close_then_refill_by_key(self, engine):
        a = _mat(6)
        s1 = _tcp(engine, name="s1")
        s1.send(a).materialize()
        s1.close()  # uniquely held: migrated, the slabs stay the payload
        assert engine.residents.stats()["migrations"] == 1
        assert isinstance(_entry(engine, a).payload, SlabPayload)
        s2 = _tcp(engine, name="s2")
        lb = s2.send(a.copy())
        np.testing.assert_array_equal(np.asarray(lb.data()), a)
        stats = s2.stats.summary()
        assert stats["send_bytes"] == 0 and stats["cross_session_reuses"] == 1
        s2.close()

    def test_governor_spill_is_free_and_refills_bit_identical(self, engine):
        mat = 64 * 48 * 4
        s = _tcp(engine, hbm_budget=mat)  # room for one matrix: spills
        gov = s.session.memgov
        a, b = _mat(7), _mat(8)
        ha = s.session.resolve(s.send(a).materialize())
        assert isinstance(ha._host_fallback, SlabPayload)
        s.send(b).materialize()
        assert ha.state == SPILLED
        # free: no copy-out job, nothing in the host store
        assert ha.id not in gov._in_flight and ha.id not in gov._host_store
        norm = float(s.run("elemental", "normest", s.send(a)).data())  # refill
        assert abs(norm - np.linalg.norm(a)) < 1e-3
        np.testing.assert_array_equal(np.asarray(s.collect(ha)), a)
        assert engine.residents.stats()["payload_copied_bytes"] == 0
        s.close()

    def test_host_collect_of_a_spilled_slab_outlives_the_free(self, engine):
        """A spilled handle's collect is served from its slab payload. On a
        CPU host the served array aliases an aligned slab, so the slab must
        not go back to the pool when the free kills the entry before the
        FETCH: the next same-shape receive would write into it."""
        _record_slabs(engine, offset=0)  # aligned: CPU puts alias
        s = _tcp(engine, hbm_budget=64 * 48 * 4)  # room for one matrix
        a, b, c = _mat(11), _mat(12), _mat(13)
        m = s.send(a).materialize()
        ha = s.session.resolve(m)
        # The send's put pinned the slab already; forget that, so the test
        # judges the collect's own pin.
        ha._host_fallback._pinned.clear()
        s.send(b).materialize()
        assert ha.state == SPILLED and ha.id not in s.session.memgov._host_store
        fut = s.collect_async(m)
        s.free(m)
        np.testing.assert_array_equal(np.asarray(s.send(c).data()), c)
        np.testing.assert_array_equal(np.asarray(fut.result()), a)
        s.close()

    def test_fleet_replay_attaches_from_adopted_slabs(self):
        """``test_kill_mid_pipeline_replays_bit_identical`` over TCP: the
        dead engine's payloads are received slabs, and the survivor's
        replay attaches from them with zero bytes re-sent."""
        from repro.fleet import FleetSupervisor
        from repro.serve.wire import TcpTransport

        rng = np.random.default_rng(9)
        a = rng.standard_normal((48, 32)).astype(np.float32)
        b = rng.standard_normal((32, 32)).astype(np.float32)

        def pipeline(sup, name, engine):
            s = sup.connect(
                name=name, engine=engine, transport=TcpTransport(sup.slot(engine).server)
            )
            s.register_library("el", ELEMENTAL)
            la, lb = s.send(a), s.send(b)
            lc = s.run("el", "gemm", la, lb)
            return s, lc, s.run("el", "gemm", lc, lb)

        def fleet(n):
            return FleetSupervisor(devices=list(jax.devices()) * n, engines=n)

        with fleet(1) as ctrl_sup:
            ctrl, _, ld = pipeline(ctrl_sup, "ctrl", list(ctrl_sup.engines)[0])
            ref = np.asarray(ctrl.collect(ld))
            ctrl.close()
        with fleet(2) as sup:
            victim = list(sup.engines)[0]
            s, lc, ld = pipeline(sup, "victim", victim)
            np.asarray(s.collect(lc))  # materialize a prefix pre-kill
            assert isinstance(_entry(s.engine, a).payload, SlabPayload)
            assert len(sup.kill(victim)) == 1
            out = np.asarray(s.collect(ld))  # replays on the survivor
            np.testing.assert_array_equal(out, ref)
            stats = s.stats.summary()
            assert stats["send_bytes"] == 0 and stats["cross_session_reuses"] >= 1
            s.close()


# ---------------------------------------------------------------------------
# a slab payload reaches the devices block by block, never joined whole
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "rows, cols, view",
    [
        ((0, 4), (0, 7), True),  # one slab, whole rows
        ((5, 7), (2, 6), True),  # inside one slab
        ((8, 10), (0, 7), True),  # the short last slab
        ((2, 9), (0, 7), False),  # straddles three slabs
        ((3, 5), (1, 4), False),  # straddles two, some columns
        ((0, 10), (0, 7), False),  # the whole matrix
    ],
    ids=["slab", "inside", "short_slab", "straddle", "straddle_cols", "whole"],
)
def test_slab_block_is_the_matrix_block(rows, cols, view):
    arr = _mat(20, (10, 7))
    staged = _staged(arr, n_shards=3)  # slabs of 4, 4 and 2 rows
    block = staged.adopt().block((slice(*rows), slice(*cols)))
    np.testing.assert_array_equal(block, arr[slice(*rows), slice(*cols)])
    assert any(np.shares_memory(block, buf) for buf in staged.buffers) is view


def test_place_puts_several_slabs_without_joining_them(monkeypatch):
    from repro.core.layouts import ROW
    from repro.core.payload import place
    from repro.core.relayout import RelayoutPlanCache
    from repro.core.sharding import single_device_mesh

    arr = _mat(21, (10, 7))
    payload = _staged(arr, n_shards=3).adopt()

    def joined(*_args, **_kw):
        raise AssertionError("a reader joined the slabs on the host")

    monkeypatch.setattr(SlabPayload, "__array__", joined)
    plan, _hit = RelayoutPlanCache().plan(arr.shape, arr.dtype, ROW, ROW, single_device_mesh())
    out, fused = place(payload, plan)
    assert not fused and out.sharding == plan.dst_sharding
    np.testing.assert_array_equal(np.asarray(out), arr)
