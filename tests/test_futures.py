"""Asynchronous task-queue engine tests: AlFuture, TaskQueue, the async ACI
(send_async/run_async/collect_async/wait), handle lifecycle states, task
failure propagation, and the relayout plan cache.

Single-device here; genuine cross-session overlap on disjoint worker groups
is measured in tests/multidevice/_concurrent_script.py. The tier2-marked
soak/stress classes at the bottom (session churn with injected failures,
leak checks) run in CI's dedicated step but are excluded from the tier-1
fast gate (pytest.ini).
"""

import threading
import time

import numpy as np
import pytest

import repro
from repro.core.errors import (
    HandleError,
    LibraryError,
    ParameterError,
    SessionError,
    TaskError,
)
from repro.core.futures import AlFuture, resolve, resolve_tree
from repro.core.handles import FAILED, FREED, MATERIALIZED
from repro.core.taskqueue import TaskQueue


@pytest.fixture()
def engine():
    return repro.AlchemistEngine()


@pytest.fixture()
def ac(engine):
    ctx = repro.AlchemistContext(engine, num_workers=1, name="async_app")
    ctx.register_library("elemental", "repro.linalg.library:ElementalLib")
    yield ctx
    ctx.stop()


# ---------------------------------------------------------------------------
# AlFuture
# ---------------------------------------------------------------------------

class TestAlFuture:
    def test_result_blocks_until_set(self):
        f = AlFuture("x")
        assert not f.done()
        threading.Timer(0.05, lambda: f._set_result(41)).start()
        assert f.result(timeout=5) == 41
        assert f.done() and f.state == "resolved"

    def test_exception_reraised_from_result(self):
        f = AlFuture("boom")
        f._set_exception(ValueError("boom"))
        with pytest.raises(ValueError, match="boom"):
            f.result()
        assert isinstance(f.exception(), ValueError)

    def test_timeout_raises_taskerror(self):
        f = AlFuture("never")
        with pytest.raises(TaskError):
            f.result(timeout=0.01)

    def test_double_resolution_rejected(self):
        f = AlFuture()
        f._set_result(1)
        with pytest.raises(TaskError):
            f._set_result(2)

    def test_done_callback_runs_on_resolution(self):
        f = AlFuture()
        seen = []
        f.add_done_callback(lambda fut: seen.append(fut.result()))
        f._set_result("v")
        assert seen == ["v"]
        # late registration fires immediately
        f.add_done_callback(lambda fut: seen.append("late"))
        assert seen == ["v", "late"]

    def test_resolve_helpers(self):
        f = AlFuture()
        f._set_result(7)
        assert resolve(f) == 7
        assert resolve(7) == 7
        g = AlFuture()
        g._set_result([f, 2, {"k": f}])
        assert resolve_tree(g) == [7, 2, {"k": 7}]


# ---------------------------------------------------------------------------
# TaskQueue
# ---------------------------------------------------------------------------

class TestTaskQueue:
    def test_fifo_ordering(self):
        q = TaskQueue("t")
        order = []
        futs = [q.submit(lambda i=i: order.append(i) or i) for i in range(20)]
        assert [f.result(5) for f in futs] == list(range(20))
        assert order == list(range(20))
        q.close()

    def test_failure_is_isolated_to_its_future(self):
        q = TaskQueue("t")

        def bad():
            raise RuntimeError("task died")

        f1 = q.submit(bad)
        f2 = q.submit(lambda: "fine")
        with pytest.raises(RuntimeError, match="task died"):
            f1.result(5)
        assert f2.result(5) == "fine"
        stats = q.stats()
        assert (stats["submitted"], stats["completed"], stats["failed"]) == (2, 1, 1)
        assert 0 <= stats["max_backlog"] <= 2  # racy: worker may drain eagerly
        q.close()

    def test_wait_ns_counts_time_queued_behind_the_worker(self):
        q = TaskQueue("t")
        gate = threading.Event()
        first = q.submit(gate.wait)
        second = q.submit(lambda: None)  # queued behind the first
        time.sleep(0.05)
        gate.set()
        first.result(5)
        second.result(5)
        assert q.stats()["wait_ns"] >= 50_000_000
        q.close()

    def test_barrier_waits_for_all(self):
        q = TaskQueue("t")
        done = []
        q.submit(lambda: (time.sleep(0.05), done.append(1)))
        q.submit(lambda: done.append(2))
        q.barrier(timeout=10)
        assert done == [1, 2]
        q.close()

    def test_submit_after_close_rejected(self):
        q = TaskQueue("t")
        q.submit(lambda: None).result(5)
        q.close()
        with pytest.raises(TaskError):
            q.submit(lambda: None)
        q.close()  # idempotent

    def test_close_drains_queued_tasks(self):
        q = TaskQueue("t")
        futs = [q.submit(lambda i=i: i) for i in range(5)]
        q.close(wait=True)
        assert [f.result(5) for f in futs] == list(range(5))


# ---------------------------------------------------------------------------
# Async ACI
# ---------------------------------------------------------------------------

class TestAsyncContext:
    def test_send_async_roundtrip(self, ac, rng):
        a = rng.standard_normal((37, 19)).astype(np.float32)
        f = ac.send_async(a, name="A")
        assert isinstance(f, repro.AlFuture)
        h = f.result(30)
        assert h.shape == (37, 19) and h.name == "A"
        np.testing.assert_allclose(np.asarray(ac.collect(h)), a, rtol=1e-6)

    def test_futures_chain_without_waiting(self, ac, rng):
        a = rng.standard_normal((24, 16)).astype(np.float32)
        b = rng.standard_normal((16, 8)).astype(np.float32)
        fa = ac.send_async(a)
        fb = ac.send_async(b)
        fc = ac.run_async("elemental", "gemm", fa, fb)
        fd = ac.collect_async(fc)
        np.testing.assert_allclose(np.asarray(fd.result(60)), a @ b, atol=1e-4)

    def test_sync_api_unchanged_on_top_of_queue(self, ac, rng):
        # the original paper-listing flow, now riding the task queue
        a = rng.standard_normal((16, 16)).astype(np.float32)
        ha = ac.send(a)
        hc = ac.run("elemental", "gemm", ha, ha)
        np.testing.assert_allclose(np.asarray(ac.collect(hc)), a @ a, atol=1e-3)
        s = ac.stats.summary()
        assert s["num_sends"] == 1 and s["num_receives"] == 1 and s["num_runs"] == 1

    def test_pending_handle_states(self, ac, rng):
        a = rng.standard_normal((64, 32)).astype(np.float32)
        f = ac.send_async(a)
        h = f.result(30)
        assert h.state == MATERIALIZED
        ac.free(h)
        assert h.state == FREED
        with pytest.raises(HandleError):
            ac.collect(h)

    def test_metadata_available_before_materialization(self, ac, rng):
        # shape/dtype are known at submit time — the AlMatrix proxy contract
        a = rng.standard_normal((128, 8)).astype(np.float32)
        f = ac.send_async(a, name="meta")
        h = f.result(30)
        assert h.num_rows == 128 and h.num_cols == 8
        assert h.nbytes() == a.nbytes

    def test_run_async_failure_propagates(self, ac, rng):
        ha = ac.send(rng.standard_normal((8, 8)).astype(np.float32))
        f = ac.run_async("elemental", "gemm", ha, object())
        with pytest.raises(ParameterError):
            f.result(30)
        # queue survives the failure
        np.testing.assert_allclose(
            np.asarray(ac.collect(ha)).shape, (8, 8)
        )

    def test_failed_send_marks_handle_failed(self, ac, monkeypatch):
        import repro.core.client as client_mod

        def boom(*a, **k):
            raise RuntimeError("transfer died")

        monkeypatch.setattr(client_mod, "timed_relayout", boom)
        f = ac.send_async(np.zeros((4, 4), dtype=np.float32))
        with pytest.raises(RuntimeError, match="transfer died"):
            f.result(30)
        # the eagerly-created handle carries the failure too
        h = ac.session.handles[max(ac.session.handles)]
        assert h.state == FAILED
        with pytest.raises(TaskError):
            h.data()

    def test_collect_freed_handle_fails_in_future(self, ac, rng):
        h = ac.send(rng.standard_normal((4, 4)).astype(np.float32))
        ac.free(h)
        assert h.state == FREED
        with pytest.raises(HandleError):
            ac.collect_async(h).result(30)

    def test_unknown_routine_fails_fast(self, ac):
        with pytest.raises(LibraryError):
            ac.run_async("elemental", "not_a_routine")
        with pytest.raises(LibraryError):
            ac.run_async("nope", "gemm")

    def test_wait_is_a_barrier(self, ac, rng):
        a = rng.standard_normal((32, 32)).astype(np.float32)
        futs = [ac.run_async("elemental", "gemm", ac.send_async(a), ac.send_async(a))
                for _ in range(3)]
        ac.wait(timeout=120)
        assert all(f.done() for f in futs)
        assert ac.stats.num_runs == 3

    def test_stop_drains_queue(self, engine, rng):
        ac = repro.AlchemistContext(engine, num_workers=1)
        ac.register_library("elemental", "repro.linalg.library:ElementalLib")
        a = rng.standard_normal((16, 16)).astype(np.float32)
        f = ac.run_async("elemental", "gemm", ac.send_async(a), ac.send_async(a))
        ac.stop()
        assert f.done()  # queued work resolved before release
        assert engine.available_workers == engine.num_workers
        with pytest.raises(SessionError):
            ac.send(a)

    def test_async_error_does_not_block_stop(self, engine):
        ac = repro.AlchemistContext(engine, num_workers=1)
        ac.register_library("elemental", "repro.linalg.library:ElementalLib")
        f = ac.run_async("elemental", "gemm", 1.0, 2.0)  # scalars: routine error
        ac.stop()
        assert f.exception() is not None


# ---------------------------------------------------------------------------
# Relayout plan cache
# ---------------------------------------------------------------------------

class TestRelayoutPlanCache:
    def test_repeat_sends_hit_cache(self, ac, rng):
        a = rng.standard_normal((64, 16)).astype(np.float32)
        ac.send(a)
        ac.send(a + 1)
        ac.send(a * 2)
        s = ac.stats.summary()
        assert s["relayout_cache_hits"] == 2
        assert s["relayout_cache_misses"] == 1

    def test_repeat_collects_hit_cache(self, ac, rng):
        a = rng.standard_normal((32, 8)).astype(np.float32)
        h1, h2 = ac.send(a), ac.send(a)
        ac.collect(h1)
        ac.collect(h2)
        s = ac.stats.summary()
        # sends: 1 miss + 1 hit; collects (reverse direction): 1 miss + 1 hit
        assert s["relayout_cache_hits"] == 2
        assert s["relayout_cache_misses"] == 2

    def test_distinct_shapes_or_dtypes_miss(self, ac, rng):
        ac.send(rng.standard_normal((16, 4)).astype(np.float32))
        ac.send(rng.standard_normal((16, 8)).astype(np.float32))
        ac.send(rng.standard_normal((16, 4)).astype(np.float16))
        assert ac.stats.relayout_cache_hits == 0
        assert ac.stats.relayout_cache_misses == 3

    def test_cached_relayout_is_correct(self, ac, rng):
        for _ in range(3):
            a = rng.standard_normal((41, 13)).astype(np.float32)
            np.testing.assert_allclose(np.asarray(ac.collect(ac.send(a))), a, rtol=1e-6)

    def test_cache_is_session_scoped(self, engine, rng):
        # Distinct payloads per session: equal bytes would attach through the
        # engine's resident store (DESIGN.md §8) and never consult the plan
        # cache via the send path at all.
        a = rng.standard_normal((16, 16)).astype(np.float32)
        ac1 = repro.AlchemistContext(engine, num_workers=1)
        ac1.send(a)
        ac1.stop()
        ac2 = repro.AlchemistContext(engine, num_workers=1)
        ac2.send(a + 1.0)
        assert ac2.stats.relayout_cache_misses == 1  # fresh cache, no hit
        ac2.stop()


# ---------------------------------------------------------------------------
# Device-pool ordering (regression: release used to fragment the pool)
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, i):
        self.id = i

    def __repr__(self):
        return f"dev{self.id}"


class _FakeSession:
    _next = iter(range(10_000, 20_000))

    def __init__(self, devs):
        self.id = next(self._next)
        self.worker_devices = devs

    def close(self):
        pass


class TestPoolOrdering:
    def _engine(self, n=8):
        return repro.AlchemistEngine(devices=[_FakeDevice(i) for i in range(n)])

    def _take(self, eng, k):
        """Allocation bookkeeping only (no Mesh — fake devices)."""
        from repro.core.scheduler import PlacementRequest

        ticket = eng.scheduler.submit(PlacementRequest(workers=k, deadline=0))
        s = _FakeSession(ticket.devices)
        eng.scheduler.bind(ticket, s.id)
        eng.sessions[s.id] = s
        return s

    def test_release_restores_canonical_order(self):
        eng = self._engine()
        s1 = self._take(eng, 2)   # devs 0-1
        s2 = self._take(eng, 3)   # devs 2-4
        s3 = self._take(eng, 3)   # devs 5-7
        # release out of allocation order
        eng.release(s2)
        eng.release(s1)
        eng.release(s3)
        assert [d.id for d in eng._free] == list(range(8))

    def test_next_allocation_gets_contiguous_prefix(self):
        eng = self._engine()
        s1 = self._take(eng, 4)
        s2 = self._take(eng, 4)
        eng.release(s1)           # devs 0-3 come back while 4-7 are out
        assert [d.id for d in eng._free] == [0, 1, 2, 3]
        eng.release(s2)
        s3 = self._take(eng, 8)
        assert [d.id for d in s3.worker_devices] == list(range(8))

    def test_interleaved_churn_never_scrambles(self):
        eng = self._engine()
        live = []
        rng = np.random.default_rng(0)
        for step in range(30):
            if live and (len(live) > 2 or rng.random() < 0.5):
                eng.release(live.pop(int(rng.integers(len(live)))))
            else:
                k = int(rng.integers(1, max(2, eng.available_workers)))
                if k <= eng.available_workers:
                    live.append(self._take(eng, k))
            ids = [d.id for d in eng._free]
            assert ids == sorted(ids), f"pool scrambled at step {step}: {ids}"
        for s in live:
            eng.release(s)
        assert [d.id for d in eng._free] == list(range(8))


# ---------------------------------------------------------------------------
# Soak / stress (tier2): many sessions churning with injected failures.
# The invariants under test: no leaked device-pool entries, no leaked
# handles, and a failed task never wedges the session's worker.
# ---------------------------------------------------------------------------

@pytest.mark.tier2
class TestTaskQueueSoak:
    def test_queue_survives_many_injected_failures(self):
        q = TaskQueue("soak")
        rng = np.random.default_rng(1)
        futs = []
        for i in range(300):
            if rng.random() < 0.3:
                def bad(i=i):
                    raise RuntimeError(f"injected-{i}")
                futs.append((q.submit(bad), True))
            else:
                futs.append((q.submit(lambda i=i: i), False))
        # every future resolves — failures isolated to their own future
        for f, should_fail in futs:
            assert (f.exception(timeout=30) is not None) == should_fail
        q.barrier(timeout=30)  # worker not wedged
        s = q.stats()
        assert s["submitted"] == 301  # 300 tasks + the barrier no-op
        assert s["completed"] + s["failed"] == s["submitted"]
        assert s["failed"] == sum(1 for _, bad in futs if bad)
        q.close(wait=True, timeout=30)
        assert not q._thread.is_alive()

    def test_failure_storm_keeps_fifo_order(self):
        q = TaskQueue("storm")
        order = []
        futs = []
        for i in range(100):
            if i % 3 == 0:
                def bad(i=i):
                    order.append(i)
                    raise ValueError(f"boom-{i}")
                futs.append(q.submit(bad))
            else:
                futs.append(q.submit(lambda i=i: order.append(i)))
        q.barrier(timeout=30)
        assert order == list(range(100))
        q.close(wait=True, timeout=30)


@pytest.mark.tier2
class TestSessionChurnSoak:
    """Sessions connecting/stopping under injected routine failures — the
    regression surface for leaked pool entries and wedged workers."""

    ROUNDS = 20

    def test_churn_with_injected_routine_failures(self, rng):
        engine = repro.AlchemistEngine()
        n_workers = engine.num_workers
        a = rng.standard_normal((16, 16)).astype(np.float32)
        bad_shape = rng.standard_normal((7, 16)).astype(np.float32)  # (16,16)@(7,16) mismatches
        sessions = []

        for i in range(self.ROUNDS):
            ac = repro.AlchemistContext(engine, num_workers=1, name=f"soak{i}")
            ac.register_library("elemental", "repro.linalg.library:ElementalLib")
            sessions.append(ac.session)
            futs, injected = [], []
            h = ac.send_async(a)
            futs.append(ac.run_async("elemental", "gemm", h, h))
            if i % 2 == 0:
                # injected failure: unpackable argument dies in the codec
                injected.append(ac.run_async("elemental", "gemm", h, object()))
            if i % 3 == 0:
                # injected failure: raises inside the queue worker itself
                injected.append(ac.session.tasks.submit(self._boom, label="injected"))
            if i % 4 == 0:
                # injected failure: shape mismatch inside the routine
                injected.append(
                    ac.run_async("elemental", "gemm", h, ac.send_async(bad_shape))
                )
            futs.append(ac.collect_async(futs[0]))
            ac.stop()

            # every future resolved (worker never wedged); good work
            # succeeded and every injected failure genuinely failed
            assert all(f.done() for f in futs + injected)
            assert all(f.exception() is None for f in futs)
            assert all(f.exception() is not None for f in injected)
            # no leaked device-pool entries, in canonical order
            assert engine.available_workers == n_workers
            assert engine._free == engine.devices
            assert ac.session.id not in engine.sessions
            # no leaked handles
            assert ac.session.closed and not ac.session.handles

        assert not engine.sessions
        # the pool is still fully allocatable after the churn
        ac = repro.AlchemistContext(engine, num_workers=n_workers, name="final")
        assert engine.available_workers == 0
        ac.stop()
        assert engine.available_workers == n_workers

    @staticmethod
    def _boom():
        raise RuntimeError("injected worker failure")

    def test_churn_with_planner_sessions(self, rng):
        """Planner-carrying sessions (resident caches holding handles) must
        release everything on stop too."""
        engine = repro.AlchemistEngine()
        n_workers = engine.num_workers
        a = rng.standard_normal((12, 12)).astype(np.float32)
        for i in range(8):
            ac = repro.AlchemistContext(engine, num_workers=1, name=f"plsoak{i}")
            ac.register_library("elemental", "repro.linalg.library:ElementalLib")
            pl = ac.planner
            lc = pl.run("elemental", "gemm", pl.send(a), pl.send(a.copy()))
            if i % 2 == 0:
                # failing DAG: the lowered future fails, the session must not
                pl.lower(pl.run("elemental", "gemm", pl.send(a), "nonsense"))
            np.testing.assert_allclose(np.asarray(pl.collect(lc)), a @ a, atol=1e-3)
            ac.stop()
            assert engine.available_workers == n_workers
            assert not ac.session.handles
        assert not engine.sessions
