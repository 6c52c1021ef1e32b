"""Rehearsals of the benchmark on the CPU, with the Pallas kernels run by the
interpreter, at sizes a test run holds: a one-chip cell in the test's own
process, a cell of more chips in a child process with as many virtual CPU
devices (:mod:`bench.tests.on_chips`)."""

import os
from contextlib import contextmanager

import jax
import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

# Per problem: sizes that keep each cell's shape (tall SVD, square GEMM) and
# its precision behaviour, small enough for the interpreter; the SVD's rows
# and columns divide a 2 x 2 grid.
SMALL = {
    "lowrank_svd": {"m": 4000, "n": 400},
    "dense_gemm": {"m": 512, "k": 512, "n": 512, "check_rows": 16},
}
CACHE_KEYS = ("jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes")


@contextmanager
def rehearsed():
    """The kernels the chip compiles, run by the interpreter; the process's
    compile cache and JAX settings left as they were."""
    from repro.kernels import ops
    from repro.launch import runtime

    saved = {key: getattr(jax.config, key) for key in CACHE_KEYS}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_BACKEND", "pallas-interpret")
        mp.setattr(runtime, "enable_compile_cache", lambda: None)
        try:
            yield
        finally:
            for key, val in saved.items():
                jax.config.update(key, val)


@pytest.fixture
def rehearsal():
    with rehearsed():
        yield


# Until the manifest holds a cell of four chips, the one-chip SVD cell is
# also rehearsed on a 2 x 2 group, so that every test run drives the
# harness's four-chip path: ``<cell>@2x2``.
ON_GRID = "svd100k.resident"


def small_cell(name: str) -> harness.Cell:
    base, _, grid = name.partition("@")
    cell = harness.load_cell(MANIFEST, base)
    cell.config.update(SMALL[cell.config["problem"]])
    if grid:
        rows, cols = (int(x) for x in grid.split("x"))
        cell.name, cell.chips, cell.config["grid"] = name, rows * cols, [rows, cols]
    return cell


def cell_names() -> list:
    import json

    with open(MANIFEST) as f:
        cells = json.load(f)["workloads"]
    names = [w["name"] for w in cells]
    if all(w["chips"] == 1 for w in cells):
        names.append(f"{ON_GRID}@2x2")
    return names
