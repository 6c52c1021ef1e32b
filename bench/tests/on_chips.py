"""A rehearsal step of a cell, run on as many devices as the cell has chips.

A step is a function of this module that takes the cell at its small size
and returns what the test asserts on, as JSON. :func:`on_its_chips` runs it
in the test's process for a one-chip cell (under the ``rehearsal`` fixture),
and for a cell of more chips in a child process whose CPU backend has that
many devices, since a process's device count is fixed when JAX starts:

    python3 -m bench.tests.on_chips '{"cell": ..., "step": ..., "kwargs": {...}}'
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

import pytest

from bench import calibrate, harness
from bench.tests.conftest import ROOT, rehearsed, small_cell


def run(cell, *, seed: int, seconds: float, trace: bool, metrics=None) -> dict:
    """One run of the cell; with ``metrics``, only those per-layer metrics.
    A metric whose reader raises ``KeyError`` (no peaks for the device) gives
    ``{"error": ...}``."""
    if metrics is not None:
        cell.per_layer = [m for m in cell.per_layer if m["name"] in metrics]
    try:
        return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace, t0=0.0)
    except KeyError as e:
        return {"error": f"KeyError: {e}"}


def control(cell, *, seed: int, seconds: float, precision: str) -> dict:
    """The program's and the control's readings of one short run."""
    return calibrate.readings(cell, seed, seconds, [precision])


def fault(cell, *, seed: int, seconds: float, routine: str, fault: str) -> dict:
    """One run with the library's ``routine`` answering as altered by the
    ``fault`` of :mod:`bench.faults`."""
    from bench.faults import FAULTS
    from repro.linalg.library import ElementalLib

    original = getattr(ElementalLib, f"_{routine}")
    alter = FAULTS[routine][fault]

    def altered(*args, mesh=None, **params):  # the engine passes a mesh to who names it
        return alter(original(*args, mesh=mesh, **params))

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ElementalLib, f"_{routine}", staticmethod(altered))
        return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False, t0=0.0)


def _svd_without_exchange(a, *, k: int = 10, oversample: int = 10, seed: int = 0, mesh=None):
    """The library's truncated SVD with the exchange between chips left out:
    each chip runs it on its own block of A alone, and U, s and V take the
    parts that the chips holding them computed."""
    import jax

    from repro.core.layouts import GRID
    from repro.linalg.lanczos import truncated_svd_lanczos

    rows, cols = GRID.partition_spec(mesh)
    spec = jax.sharding.PartitionSpec
    local = functools.partial(
        truncated_svd_lanczos, k=int(k), oversample=int(oversample), seed=int(seed)
    )
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=spec(rows, cols),
        out_specs=(spec(rows, None), spec(), spec(cols, None)),
        check_vma=False,
    )(a)


# By routine: the routine run on several chips with their exchange left out.
UNEXCHANGED = {"truncated_svd": _svd_without_exchange}


def unexchanged(cell, *, seed: int, seconds: float, routine: str) -> dict:
    """One run with the library's ``routine`` run with no exchange between
    the chips (:data:`UNEXCHANGED`)."""
    from repro.linalg.library import ElementalLib

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ElementalLib, f"_{routine}", staticmethod(UNEXCHANGED[routine]))
        return harness.run_cell(cell, seed=seed, seconds=seconds, trace=False, t0=0.0)


def on_its_chips(name: str, step: str, **kwargs) -> dict:
    """``step(small_cell(name), **kwargs)`` on the cell's chip count."""
    cell = small_cell(name)
    if cell.chips == 1:
        return globals()[step](cell, **kwargs)
    env = dict(os.environ)
    env.pop("REPRO_FORCE_PALLAS", None)
    env["JAX_PLATFORMS"] = "cpu"
    # Each device on one thread of its own, so the child leaves the test
    # run's other workers their cores.
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={cell.chips} --xla_cpu_multi_thread_eigen=false"
    )
    paths = [ROOT, os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")]
    env["PYTHONPATH"] = os.pathsep.join(paths)
    spec = json.dumps({"cell": name, "step": step, "kwargs": kwargs})
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.on_chips", spec],
        cwd=ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(spec: str) -> int:
    spec = json.loads(spec)
    cell = small_cell(spec["cell"])
    import jax

    assert len(jax.devices()) >= cell.chips, jax.devices()
    with rehearsed():
        out = globals()[spec["step"]](cell, **spec["kwargs"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
