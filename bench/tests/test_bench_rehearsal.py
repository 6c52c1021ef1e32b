"""Each cell's job loop and check, rehearsed in-process on the CPU at a small
size over TCP, and the command's refusals to measure anywhere but on a TPU."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, problems
from bench.tests.conftest import ROOT, cell_names, small_cell
from bench.tests.on_chips import on_its_chips

SEED = 2**31 + 12345  # past 32 signed bits: any whole seed is taken


@pytest.mark.parametrize("name", cell_names())
def test_cell_runs_and_checks_correct(rehearsal, name):
    cell = small_cell(name)
    out = on_its_chips(name, "run", seed=SEED, seconds=0.5, trace=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    assert list(out)[-1] == "checks"
    assert out["window_compiles"] == 0


@pytest.mark.parametrize("name", cell_names())
def test_traced_run_reads_its_layers(rehearsal, name):
    cell = small_cell(name)
    rooflines = [m["name"] for m in cell.per_layer if m["name"].endswith("_roofline")]
    layers = [m["name"] for m in cell.per_layer if m["name"] not in rooflines]
    out = on_its_chips(name, "run", seed=SEED + 1, seconds=0.5, trace=True, metrics=layers)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == set(layers)
    assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
    ops, idle = out["breakdown"]["device_ops"], out["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(idle) <= 10
    assert {name for name, _ in idle} <= {"bench.send", "bench.run", "bench.collect",
                                          "bench.free", "bench.between"}
    if rooflines:
        # A roofline needs the peaks of a known chip: never one of the CPU's.
        out = on_its_chips(name, "run", seed=SEED + 2, seconds=0.2, trace=True, metrics=rooflines)
        assert "no peaks" in out["error"]


def test_seeds_give_the_same_operands(rehearsal):
    cfg = small_cell("gemm10k.roundtrip").config
    a = problems.load(cfg["problem"]).build(cfg, SEED)
    b = problems.load(cfg["problem"]).build(cfg, SEED)
    c = problems.load(cfg["problem"]).build(cfg, SEED + 1)
    assert np.array_equal(a.operands["A"], b.operands["A"])
    assert not np.array_equal(a.operands["A"], c.operands["A"])
    first = {k: v.copy() for k, v in a.fresh(7).items()}
    assert not np.array_equal(first["A"], a.fresh(8)["A"])
    assert np.array_equal(first["A"], a.inputs(7)["A"])


def _env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("REPRO_FORCE_PALLAS", None)
    env.update(extra)
    return env


def _bench(cwd, env):
    cmd = [sys.executable, "bench/run.py", "--workload", "gemm10k.resident", "--seed", "1",
           "--seconds", "1", "--trace", "0"]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_host_without_tpu():
    proc = _bench(ROOT, _env())
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_refuses_interpret_mode_kernels():
    proc = _bench(ROOT, _env(REPRO_FORCE_PALLAS="interpret"))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "REPRO_FORCE_PALLAS" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench(tmp_path, _env(PYTHONPATH=""))
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell(os.path.join(ROOT, "BENCHMARK.json"), "no.such.cell")


def test_result_line_is_json_with_checks_last(rehearsal):
    out = harness.run_cell(small_cell("gemm10k.resident"), seed=3, seconds=0.2, trace=False,
                           t0=0.0)
    line = json.loads(json.dumps(out))
    assert list(line)[-1] == "checks"
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
