"""Run one cell of ``BENCHMARK.json`` once: set-up, the measured window, the
check, and the result line.

A cell names a configuration (``bench/configs/<config>.json``, whose
``problem`` names the module in ``bench/problems/`` that builds its operands
and holds its reference) and a traffic mix (``bench/traffic/<traffic>.json``,
read by :mod:`bench.loop`). Each metric is read by ``bench/metrics/<name>.py``
from the run's record. Adding a cell, a configuration, a traffic mix or a
metric adds files and manifest entries; nothing here changes.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic_name: str
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(manifest_path: str, name: str) -> Cell:
    """The cell ``name`` of the manifest, with its configuration and the
    metrics it reports."""
    with open(manifest_path) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {manifest_path}; have {sorted(cells)}")
    w = cells[name]
    (cfg_entry,) = [c for c in manifest["configs"] if c["name"] == w["config"]]
    root = os.path.dirname(os.path.abspath(manifest_path))
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    return Cell(
        name=name,
        chips=w["chips"],
        config=config,
        traffic_name=w["traffic"],
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
    )


def device_refusal(chips: int) -> str | None:
    """Why this process may not measure, or None: the benchmark runs on TPU
    chips with the compiled Pallas kernels, never on a fallback."""
    if os.environ.get("REPRO_FORCE_PALLAS"):
        return "REPRO_FORCE_PALLAS is a test knob; unset it to measure the chip"
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"no TPU: jax sees {devices[0].platform} devices only"
    if len(devices) < chips:
        return f"the cell needs {chips} chips and jax sees {len(devices)}"
    from repro.kernels import ops

    if ops.backend() != "pallas":
        return f"kernels would run as {ops.backend()!r}, not compiled Pallas"
    return None


def reader_path(name: str) -> str:
    """``bench/metrics/<name>.py``; for a quantity split by the cells that
    report it (``job_s.svd``), the reader of the quantity (``job_s``)."""
    base = name
    while True:
        path = os.path.join(BENCH, "metrics", f"{base}.py")
        if os.path.exists(path) or "." not in base:
            return path
        base = base.rsplit(".", 1)[0]


def load_reader(name: str):
    """``read(run)`` of the metric ``name`` (see :func:`reader_path`)."""
    path = reader_path(name)
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Run:
    """What one run recorded, for the metric readers."""

    cell: Cell
    device_kind: str
    setup_s: float
    window_s: float
    latencies: list
    spans: list  # bench.loop.Span, inside the window
    trace: object = None  # bench.trace.Summary, with --trace 1

    @property
    def jobs(self) -> int:
        return len(self.latencies)


class Reservoir:
    """A uniform sample of ``size`` jobs of a stream of unknown length, drawn
    from the seed (Algorithm R)."""

    def __init__(self, size: int, seed: int):
        from bench.problems import rng

        self.size, self.rng, self.seen = size, rng(seed, 4), 0

    def offer(self) -> int | None:
        """The slot the next job takes, or None."""
        t, self.seen = self.seen, self.seen + 1
        if t < self.size:
            return t
        r = int(self.rng.integers(0, t + 1))
        return r if r < self.size else None


def _compile_counter():
    """A list that grows by one for each backend compile from now on."""
    import jax

    seen: list = []

    def listener(event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def _memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()[:chips]]
    return int(max(peaks))


@dataclass
class Measurement:
    """One run of a cell: its record, and what the check keeps."""

    run: Run
    problem: object
    collect: str  # the traffic's collect: which limits apply
    fresh: bool  # whether each job sent operands of its own
    kept: list  # (job, retained), in job order
    attempted: int
    failed: int
    memory_peak: int
    window_compiles: int


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, t0: float) -> dict:
    """Run the cell once; returns the result object. ``t0`` is the process's
    start on the ``time.monotonic`` clock."""
    return result(measure(cell, seed=seed, seconds=seconds, trace=trace, t0=t0))


def measure(
    cell: Cell, *, seed: int, seconds: float, trace: bool, t0: float, save_trace: str = ""
) -> Measurement:
    """Set-up, the window, and teardown; the program's state is freed when
    this returns. ``save_trace`` keeps a copy of the trace file there."""
    import jax

    import repro
    from repro.launch import runtime
    from repro.serve.wire import server_for

    from bench import loop, problems
    from bench import trace as tracemod

    runtime.enable_compile_cache()
    # Cache every program, however quick to compile: only a cell's first run
    # in a checkout may compile.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    cfg = cell.config
    traffic = loop.Traffic.load(os.path.join(BENCH, "traffic", f"{cell.traffic_name}.json"))
    problem = problems.load(cfg["problem"]).build(cfg, seed)
    engine = repro.AlchemistEngine()
    session = repro.connect(engine, grid=tuple(cfg["grid"]), transport="tcp")
    session.register_library("elemental", "repro.linalg.library:ElementalLib")
    client = loop.Client(session, problem, traffic)
    compiles = _compile_counter()
    reservoir = Reservoir(traffic.check_jobs, seed)
    kept: dict = {}
    latencies: list = []
    attempted = failed = 0
    log_dir = ""
    try:
        with session.policy("eager"):
            client.setup()
            for j in range(traffic.warm_jobs):
                client.job(j)
            setup_s = time.monotonic() - t0
            client.spans.clear()
            compiled_in_setup = len(compiles)
            if trace:
                log_dir = tempfile.mkdtemp(prefix="bench-trace-")  # under TMPDIR
                # Host spans only: the Python tracer would slow every call it sees.
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(log_dir, profiler_options=opts)
            j = traffic.warm_jobs
            with jax.profiler.TraceAnnotation(tracemod.WINDOW):
                start = time.perf_counter()
                while time.perf_counter() - start < seconds:
                    attempted += 1
                    t = time.perf_counter()
                    try:
                        collected = client.job(j)
                    except Exception as e:  # a job that fails ends the window
                        failed += 1
                        print(f"bench: job {j} failed: {e!r}", file=sys.stderr)
                        break
                    latencies.append(time.perf_counter() - t)
                    slot = reservoir.offer()
                    if slot is not None:
                        kept[slot] = (j, problem.retain(j, collected))
                    del collected
                    j += 1
                window_s = time.perf_counter() - start
            if trace:
                jax.profiler.stop_trace()
            window_compiles = len(compiles) - compiled_in_setup
            memory_peak = _memory_peak(cell.chips)
            client.close()
    finally:
        session.close()
        srv = server_for(engine)
        if srv is not None:
            srv.stop()
        engine.shutdown()
    spans = client.spans
    del client, session, engine
    gc.collect()

    summary = None
    if trace:
        path = tracemod.find_xplane(log_dir)
        if save_trace:
            shutil.copy(path, save_trace)
        summary = tracemod.reduce(path, chips=cell.chips)
        shutil.rmtree(log_dir, ignore_errors=True)
    run = Run(
        cell=cell,
        device_kind=jax.devices()[0].device_kind,
        setup_s=setup_s,
        window_s=window_s,
        latencies=latencies,
        spans=spans,
        trace=summary,
    )
    return Measurement(
        run=run,
        problem=problem,
        collect=traffic.collect,
        fresh=traffic.operands == "fresh",
        kept=sorted(kept.values(), key=lambda job_kept: job_kept[0]),
        attempted=attempted,
        failed=failed,
        memory_peak=memory_peak,
        window_compiles=window_compiles,
    )


def result(m: Measurement) -> dict:
    """The result object of a measured run: the check against the reference,
    and the cell's metrics (its per-layer ones where the run was traced)."""
    import jax

    run, cell = m.run, m.run.cell
    checks = check(m.problem, m.kept, cell.config["limits"][m.collect])
    correct = (
        m.failed == 0 and bool(m.kept) and all(c["value"] <= c["limit"] for c in checks.values())
    )
    traced = run.trace is not None
    metrics = {}
    for spec in cell.per_layer if traced else cell.end_to_end:
        value = load_reader(spec["name"])(run)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": m.memory_peak,
    }
    out = {"correct": correct, "attempted": m.attempted, "failed": m.failed, "metrics": metrics}
    out["device"] = device
    out["window_compiles"] = m.window_compiles
    if traced:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": [list(kv) for kv in run.trace.top_ops()],
            "idle_gaps": [list(kv) for kv in run.trace.top_idle()],
        }
    out["checks"] = checks  # last: the numbers compared, each beside its limit
    return out


def check(problem, retained: list, limits: dict) -> dict:
    """Each number compared, beside its limit. A reading the check could not
    make (no job kept, a malformed answer) reads as infinite."""
    readings = problem.check(retained) if retained else {}
    return {
        name: {"value": float(readings.get(name, float("inf"))), "limit": float(limit)}
        for name, limit in limits.items()
    }


def p95(values: list) -> float:
    """The 95th percentile of all values (inclusive quantiles)."""
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=20, method="inclusive")[18]
