"""Engine behaviour tests: sessions, handles, transfers, library calls.

Single-device here; the multi-device engine semantics (worker groups,
genuine relayout traffic) are covered by tests/multidevice/.
"""

import numpy as np
import pytest

import repro
from repro.core.errors import (
    HandleError,
    LibraryError,
    SessionError,
    WorkerAllocationError,
)


@pytest.fixture()
def engine():
    return repro.AlchemistEngine()


@pytest.fixture()
def ac(engine):
    ctx = repro.AlchemistContext(engine, num_workers=1, name="test_app")
    yield ctx
    ctx.stop()


class TestSessions:
    def test_connect_allocates_workers(self, engine):
        ac = repro.AlchemistContext(engine, num_workers=1)
        assert engine.available_workers == engine.num_workers - 1
        ac.stop()
        assert engine.available_workers == engine.num_workers

    def test_overallocation_raises(self, engine):
        with pytest.raises(WorkerAllocationError):
            repro.AlchemistContext(engine, num_workers=engine.num_workers + 1)

    def test_stopped_context_rejects_use(self, engine):
        ac = repro.AlchemistContext(engine, num_workers=1)
        ac.stop()
        with pytest.raises(SessionError):
            ac.send(np.eye(3))

    def test_double_stop_is_idempotent(self, engine):
        ac = repro.AlchemistContext(engine, num_workers=1)
        ac.stop()
        ac.stop()

    def test_context_manager(self, engine):
        with repro.AlchemistContext(engine, num_workers=1) as ac:
            ac.send(np.eye(2))
        assert engine.available_workers == engine.num_workers


class TestHandles:
    def test_send_collect_roundtrip(self, ac, rng):
        a = rng.standard_normal((37, 19)).astype(np.float32)
        h = ac.send(a, name="A")
        assert h.shape == (37, 19)
        assert h.name == "A"
        back = np.asarray(ac.collect(h))
        np.testing.assert_allclose(back, a, rtol=1e-6)

    def test_handles_are_session_scoped(self, engine, rng):
        # paper: each application has its own matrix namespace
        ac1 = repro.AlchemistContext(engine, num_workers=1)
        h = ac1.send(rng.standard_normal((4, 4)))
        ac1.stop()
        ac2 = repro.AlchemistContext(engine, num_workers=1)
        with pytest.raises(HandleError):
            ac2.collect(h)
        ac2.stop()

    def test_freed_handle_rejected(self, ac, rng):
        h = ac.send(rng.standard_normal((4, 4)))
        ac.free(h)
        with pytest.raises(HandleError):
            ac.collect(h)

    def test_send_requires_2d(self, ac):
        with pytest.raises(SessionError):
            ac.send(np.zeros(5))

    def test_transfer_stats_accumulate(self, ac, rng):
        a = rng.standard_normal((16, 8)).astype(np.float32)
        h = ac.send(a)
        ac.collect(h)
        s = ac.stats.summary()
        assert s["num_sends"] == 1
        assert s["num_receives"] == 1
        assert s["send_bytes"] == a.nbytes
        assert s["recv_bytes"] == a.nbytes

    def test_transfer_log_stays_bounded(self, ac, rng):
        from repro.core.session import TRANSFER_LOG

        a = rng.standard_normal((8, 4)).astype(np.float32)
        h = ac.send(a)
        first = ac.stats.transfers[-1]
        n = TRANSFER_LOG + 10
        for _ in range(n):
            ac.stats.record_transfer(first)
        ac.collect(h)
        assert len(ac.stats.transfers) == TRANSFER_LOG
        assert ac.stats.transfers[-1].direction == "receive"  # the latest, as readers take it
        s = ac.stats.summary()  # the counters still cover every transfer
        assert (s["num_sends"], s["num_receives"]) == (n + 1, 1)


class TestLibraries:
    def test_register_by_import_path(self, ac):
        # the "dlopen at runtime" analogue
        lib = ac.register_library("elemental", "repro.linalg.library:ElementalLib")
        assert "truncated_svd" in lib.routine_names()

    def test_unknown_library_raises(self, ac):
        with pytest.raises(LibraryError):
            ac.run("nope", "gemm")

    def test_unknown_routine_raises(self, ac):
        ac.register_library("elemental", "repro.linalg.library:ElementalLib")
        with pytest.raises(LibraryError):
            ac.run("elemental", "not_a_routine")

    def test_bad_import_path(self, ac):
        with pytest.raises(LibraryError):
            ac.register_library("x", "repro.not_a_module:Nothing")
        with pytest.raises(LibraryError):
            ac.register_library("x", "repro.linalg.library:NotAClass")

    def test_gemm_via_engine(self, ac, rng):
        ac.register_library("elemental", "repro.linalg.library:ElementalLib")
        a = rng.standard_normal((24, 16)).astype(np.float32)
        b = rng.standard_normal((16, 8)).astype(np.float32)
        ha, hb = ac.send(a), ac.send(b)
        hc = ac.run("elemental", "gemm", ha, hb)
        np.testing.assert_allclose(np.asarray(ac.collect(hc)), a @ b, atol=1e-4)

    def test_chained_calls_do_not_transfer(self, ac, rng):
        # the AlMatrix residency contract: only collect() moves bulk data
        ac.register_library("elemental", "repro.linalg.library:ElementalLib")
        a = rng.standard_normal((16, 16)).astype(np.float32)
        ha = ac.send(a)
        before = ac.stats.num_sends + ac.stats.num_receives
        h2 = ac.run("elemental", "gemm", ha, ha)
        h3 = ac.run("elemental", "gemm", h2, ha)
        assert (ac.stats.num_sends + ac.stats.num_receives) == before
        np.testing.assert_allclose(
            np.asarray(ac.collect(h3)), a @ a @ a, atol=1e-3
        )

    def test_scalar_outputs_return_to_driver(self, ac, rng):
        ac.register_library("elemental", "repro.linalg.library:ElementalLib")
        a = rng.standard_normal((32, 8)).astype(np.float32)
        ha = ac.send(a)
        norm = ac.run("elemental", "normest", ha)
        assert isinstance(norm, np.ndarray)
        np.testing.assert_allclose(float(norm), np.linalg.norm(a), rtol=1e-4)

    def test_compute_time_recorded(self, ac, rng):
        ac.register_library("elemental", "repro.linalg.library:ElementalLib")
        ha = ac.send(rng.standard_normal((16, 16)).astype(np.float32))
        ac.run("elemental", "gemm", ha, ha)
        assert ac.stats.compute_seconds > 0
        assert ac.stats.num_runs == 1
