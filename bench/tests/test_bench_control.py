"""The comparison that decides ``correct`` fails what it must: the reference
computed one precision step below the configuration's, put in the program's
place, and the program with its answer altered where it is produced. At the
cells' own size the same control runs on the chip (bench/calibrate.py)."""

import pytest

from bench import harness, problems
from bench.faults import FAULTS
from bench.tests.conftest import cell_names, small_cell
from bench.tests.on_chips import UNEXCHANGED, on_its_chips

SEED = 2**31 + 777


@pytest.mark.parametrize("name", cell_names())
def test_control_fails_and_program_passes(rehearsal, name):
    cell = small_cell(name)
    precision = cell.config["control"]
    rec = on_its_chips(name, "control", seed=SEED, seconds=0.3, precision=precision)
    limits = cell.config["limits"][rec["collect"]]
    program, control = rec["program"], rec[f"control_{precision}"]
    assert all(program[key] <= limit for key, limit in limits.items()), (program, limits)
    assert any(control[key] > limit for key, limit in limits.items()), (control, limits)


ROUTINES = {"dense_gemm": "gemm", "lowrank_svd": "truncated_svd"}


def _faults():
    out = []
    for name in cell_names():
        routine = ROUTINES[small_cell(name).config["problem"]]
        out += [pytest.param(name, routine, f, id=f"{name}-{f}") for f in FAULTS[routine]]
    return out


@pytest.mark.parametrize("name,routine,fault", _faults())
def test_an_answer_altered_where_produced_is_not_correct(rehearsal, name, routine, fault):
    out = on_its_chips(name, "fault", seed=SEED + 1, seconds=0.3, routine=routine, fault=fault)
    assert out["failed"] == 0 and not out["correct"], out["checks"]


def _unexchanged():
    out = []
    for name in cell_names():
        cell = small_cell(name)
        routine = ROUTINES[cell.config["problem"]]
        if cell.chips > 1 and routine in UNEXCHANGED:
            out.append(pytest.param(name, routine, id=name))
    return out


@pytest.mark.parametrize("name,routine", _unexchanged())
def test_the_exchange_between_chips_left_out_is_not_correct(rehearsal, name, routine):
    out = on_its_chips(name, "unexchanged", seed=SEED + 3, seconds=0.3, routine=routine)
    assert out["failed"] == 0 and not out["correct"], out["checks"]


def test_a_job_that_fails_is_counted_and_not_correct(rehearsal, monkeypatch):
    from repro.linalg.library import ElementalLib

    cell = small_cell("gemm10k.resident")
    warm = harness_traffic(cell).warm_jobs
    original, calls = ElementalLib._normest, []

    def fails_after_warm_up(*a, **kw):
        calls.append(None)
        if len(calls) > warm:
            raise RuntimeError("injected fault")
        return original(*a, **kw)

    monkeypatch.setattr(ElementalLib, "_normest", staticmethod(fails_after_warm_up))
    out = harness.run_cell(cell, seed=SEED + 2, seconds=0.3, trace=False, t0=0.0)
    assert out["attempted"] == 1 and out["failed"] == 1 and not out["correct"]


def harness_traffic(cell):
    import os

    from bench import loop

    return loop.Traffic.load(os.path.join(harness.BENCH, "traffic", f"{cell.traffic_name}.json"))


def test_svd_check_fails_wrong_answers(rehearsal):
    cell = small_cell("svd100k.resident")
    limits = cell.config["limits"]["outputs"]
    problem = problems.load("lowrank_svd").build(cell.config, SEED)
    ref = problem.exact()
    u, s, v = ref.u.astype("float32"), ref.sigma.astype("float32"), ref.v.astype("float32")

    def correct(u, s, v):
        readings = problem.check([(0, (u, s, v))])
        return all(readings.get(key, float("inf")) <= lim for key, lim in limits.items())

    assert correct(u, s, v)
    swapped = s.copy()
    swapped[[3, 4]] = swapped[[4, 3]]
    assert not correct(u, swapped, v)
    assert not correct(u, s * 1.01, v)
    assert not correct(u, s[:-1], v)
    assert not correct(u * float("nan"), s, v)


def test_gemm_check_fails_wrong_answers(rehearsal):
    cell = small_cell("gemm10k.roundtrip")
    limits = cell.config["limits"]
    problem = problems.load("dense_gemm").build(cell.config, SEED)
    a, b = (x.astype("float64") for x in problem.inputs(0).values())
    c = (a @ b).astype("float32")

    def correct(collected, collect):
        readings = problem.check([(0, problem.retain(0, collected))])
        return all(readings.get(k, float("inf")) <= lim for k, lim in limits[collect].items())

    assert correct({"C": c}, "outputs")
    assert not correct({"C": c * 1.02}, "outputs")
    assert not correct({"C": c.T.copy()}, "outputs")
    assert not correct({"C": c[:, :-1]}, "outputs")
    fro = float(((a @ b) ** 2).sum() ** 0.5)
    assert correct({"normest": fro}, "normest")
    assert not correct({"normest": fro * 1.001}, "normest")
