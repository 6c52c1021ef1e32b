"""chip_smoke.py rehearsed on the CPU: its phases end to end over TCP at tiny
sizes with the Pallas kernels in interpret mode, its checks against wrong
answers, and its refusals to report a result without a TPU."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import repro
from repro.kernels import ops

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


@pytest.fixture
def engine(monkeypatch):
    # The kernel bodies the chip compiles, run by the interpreter.
    monkeypatch.setattr(ops, "_BACKEND", "pallas-interpret")
    eng = repro.AlchemistEngine()
    yield eng
    eng.shutdown()


def test_svd_phase_end_to_end(engine):
    rec = chip_smoke.phase_svd(engine, (1, 1), m=700, n=96, k=20, seed=3)
    assert rec["ok"], rec
    assert rec["shapes_ok"] and rec["finite"]
    assert rec["sigma_max_abs_err"] < 1e-3  # f32 on the CPU: far inside the bf16 bound
    assert rec["devices"] == [0]
    for key in ("build_s", "send_s", "run_cold_s", "run_warm_s", "collect_s", "check_s"):
        assert rec[key] >= 0


def test_gemm_phase_end_to_end(engine):
    rec = chip_smoke.phase_gemm(engine, (1, 1), n=384, seed=1)
    assert rec["ok"], rec
    assert rec["max_err_over_bound"] < 1.0
    assert rec["rel_fro_err"] < 1e-5  # f32 kernel body on the CPU


def test_svd_check_fails_wrong_answers():
    problem = chip_smoke.LowRankProblem(300, 80, 20, seed=0)
    u, s, vt = np.linalg.svd(problem.matrix().astype(np.float64), full_matrices=False)
    u, s, v = u[:, :20], s[:20], vt[:20].T
    assert chip_smoke.check_svd(problem, u, s, v)["ok"]
    swapped = s.copy()
    swapped[[3, 4]] = swapped[[4, 3]]
    assert not chip_smoke.check_svd(problem, u, swapped, v)["ok"]
    assert not chip_smoke.check_svd(problem, u, s + 0.5, v)["ok"]
    assert not chip_smoke.check_svd(problem, np.zeros_like(u), s, v)["ok"]
    assert not chip_smoke.check_svd(problem, u[:, ::-1], s, v)["ok"]
    assert not chip_smoke.check_svd(problem, u, s, v[:-1])["ok"]


def test_gemm_check_fails_wrong_answers():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 64), dtype=np.float32)
    b = rng.standard_normal((64, 64), dtype=np.float32)
    c = a.astype(np.float64) @ b.astype(np.float64)
    rows = np.arange(8)
    assert chip_smoke.check_gemm(a, b, c, rows)["ok"]
    assert not chip_smoke.check_gemm(a, b, c * 1.02, rows)["ok"]
    assert not chip_smoke.check_gemm(a, b, a @ b.T, rows)["ok"]


FOUR_DEVICE_SCRIPT = r"""
import chip_smoke, repro
from repro.kernels import ops
assert ops.backend() == "pallas-interpret"
engine = repro.AlchemistEngine()
assert engine.num_workers == 4
svd = chip_smoke.phase_svd(engine, (2, 2), m=602, n=96, k=20, seed=1)
gemm = chip_smoke.phase_gemm(engine, (2, 2), n=200, seed=1)
uneven = chip_smoke.phase_uneven(engine, (2, 2), shape=(43, 96), seed=1)
for rec in (svd, gemm, uneven):
    assert rec["ok"], rec
assert svd["devices"] == [0, 1, 2, 3] and uneven["attach_devices"] == [0, 1, 2, 3]
engine.shutdown()
print("FOUR_DEVICE_PHASES_OK")
"""


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([SRC, ROOT, env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def _run(*cmd, env, cwd=ROOT, timeout=300):
    return subprocess.run(
        [sys.executable, *cmd], cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout
    )


def test_four_chip_phases_on_virtual_devices():
    # The --chips 4 phases on a 2x2 group of virtual CPU devices: sharded
    # SVD, SUMMA through the kernel, and the uneven send placed once from the
    # wire and once through the fused pad kernel.
    env = _env(
        XLA_FLAGS="--xla_force_host_platform_device_count=4", REPRO_FORCE_PALLAS="interpret"
    )
    proc = _run("-c", FOUR_DEVICE_SCRIPT, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FOUR_DEVICE_PHASES_OK" in proc.stdout


def test_smoke_refuses_a_host_without_tpu():
    proc = _run("chip_smoke.py", env=_env())
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_smoke_refuses_forced_interpret_mode():
    proc = _run("chip_smoke.py", env=_env(REPRO_FORCE_PALLAS="interpret"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_smoke_refuses_outside_the_repository(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run("chip_smoke.py", env=_env(PYTHONPATH=""), cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


CACHE_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.launch import runtime
print(runtime.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
"""


def test_compile_cache_honours_the_environment(tmp_path):
    target = tmp_path / "cache"
    proc = _run("-c", CACHE_SCRIPT, env=_env(JAX_COMPILATION_CACHE_DIR=str(target)))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split()[-1] == str(target)
    assert any(target.iterdir())


def test_compile_cache_defaults_to_the_checkout():
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = _run("-c", CACHE_SCRIPT, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    expected = os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert proc.stdout.split()[-1] == expected
    assert os.listdir(expected)


def test_host_memory_guard_reads_this_host():
    with open("/proc/meminfo") as f:
        total = next(int(ln.split()[1]) * 1024 for ln in f if ln.startswith("MemTotal:"))
    assert 0 < chip_smoke.host_bytes_free() <= total
