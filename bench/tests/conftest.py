"""Rehearsals of the benchmark on the CPU: in-process, with the Pallas kernels
run by the interpreter, at sizes a test run holds."""

import os

import jax
import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

# Per problem: sizes that keep each cell's shape (tall SVD, square GEMM) and
# its precision behaviour, small enough for the interpreter.
SMALL = {
    "lowrank_svd": {"m": 4000, "n": 400},
    "dense_gemm": {"m": 512, "k": 512, "n": 512, "check_rows": 16},
}
CACHE_KEYS = ("jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def rehearsal(monkeypatch):
    """The kernels the chip compiles, run by the interpreter; the process's
    compile cache and JAX settings left as they were."""
    from repro.kernels import ops
    from repro.launch import runtime

    monkeypatch.setattr(ops, "_BACKEND", "pallas-interpret")
    monkeypatch.setattr(runtime, "enable_compile_cache", lambda: None)
    saved = {key: getattr(jax.config, key) for key in CACHE_KEYS}
    yield
    for key, val in saved.items():
        jax.config.update(key, val)


def small_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(MANIFEST, name)
    cell.config.update(SMALL[cell.config["problem"]])
    return cell


def cell_names() -> list:
    import json

    with open(MANIFEST) as f:
        return [w["name"] for w in json.load(f)["workloads"]]
