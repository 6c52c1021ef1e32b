"""Multi-device semantics, run in subprocesses so the forced host-device
count never leaks into this test process (smoke tests must see 1 device)."""

import os
import subprocess
import sys


HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")


def _run(script: str, marker: str, extra_env=None) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if extra_env:
        env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "multidevice", script)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    assert marker in proc.stdout, proc.stdout[-2000:]


def test_engine_worker_groups_and_distributed_linalg():
    _run("_engine_script.py", "MULTIDEVICE_ENGINE_OK")


def test_concurrent_sessions_overlap():
    _run("_concurrent_script.py", "MULTIDEVICE_CONCURRENT_OK")


def test_padded_sends_roundtrip_arbitrary_shapes():
    _run("_padding_script.py", "MULTIDEVICE_PADDING_OK")


def test_sharded_models_match_single_device():
    _run("_model_script.py", "MULTIDEVICE_MODEL_OK")


def test_queued_all_free_request_size_is_pinned():
    _run("_admission_script.py", "MULTIDEVICE_ADMISSION_OK")


def test_multi_shard_payload_is_adopted_and_recycled():
    _run("_adoption_script.py", "MULTIDEVICE_ADOPTION_OK")
