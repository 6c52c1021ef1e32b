"""Readings that the limits are set from: the program's and the controls'.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--controls int8,fp8]
                               [--control-seeds 3] [--seconds 2] [--save-trace <path>]

For each seed, one process runs the cell as the benchmark does, with a short
window, and prints the check's readings of what the program produced; then,
on the first ``--control-seeds`` seeds, for each control precision, the
reference computed at that precision in the program's place on the same
jobs' inputs, and each fault of ``bench/faults.py`` applied to the answers
the program kept, read by the same check. One JSON line per seed. The
benchmark's own runs never run this. It needs the chip.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_readings(m, precision: str) -> dict:
    """The check's readings of the control at ``precision``, on the inputs of
    the jobs the run kept (one job stands for all where they share operands)."""
    jobs = [job for job, _ in m.kept][: None if m.fresh else 1]
    retained = []
    for job in jobs:
        collected = m.problem.control(job, m.collect, precision)
        retained.append((job, m.problem.retain(job, collected)))
    return m.problem.check(retained)


def fault_readings(m) -> dict:
    """The check's readings of the kept answers under each fault of the
    routine, where the run kept whole answers (not a norm of them)."""
    from bench.faults import FAULTS, alter

    if m.collect != "outputs":
        return {}
    faults = FAULTS[m.problem.routine.name]
    return {
        f"fault_{name}": m.problem.check([(job, alter(kept, f)) for job, kept in m.kept])
        for name, f in faults.items()
    }


def readings(cell, seed: int, seconds: float, controls, save_trace: str = "") -> dict:
    from bench import harness

    t0 = time.monotonic()
    m = harness.measure(
        cell, seed=seed, seconds=seconds, trace=bool(save_trace), t0=t0, save_trace=save_trace
    )
    t = time.monotonic()
    program = m.problem.check(m.kept)
    out = {
        "seed": seed,
        "collect": m.collect,
        "jobs": m.run.jobs,
        "job_s": m.run.window_s / max(m.run.jobs, 1),
        "setup_s": m.run.setup_s,
        "program": program,
        "check_s": time.monotonic() - t,
        "memory_peak_bytes": m.memory_peak,
    }
    for precision in controls:
        t = time.monotonic()
        out[f"control_{precision}"] = control_readings(m, precision)
        out[f"control_{precision}_s"] = time.monotonic() - t
    if controls:
        out.update(fault_readings(m))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", default="", help="comma-separated precisions")
    ap.add_argument("--control-seeds", type=int, default=3, help="seeds that read the controls")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--save-trace", default="", help="copy the first seed's trace here")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.load_cell(os.path.join(ROOT, "BENCHMARK.json"), args.workload)
    refusal = harness.device_refusal(cell.chips)
    if refusal:
        print(f"calibrate: {refusal}", file=sys.stderr)
        return 2
    controls = [c for c in args.controls.split(",") if c]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rec = readings(
            cell,
            seed,
            args.seconds,
            controls if i < args.control_seeds else [],
            args.save_trace if i == 0 else "",
        )
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
