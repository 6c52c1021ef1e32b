"""Benchmark harness — one module per paper table/figure.

  gemm_table1        Table 1  (matrix multiply, Spark vs Spark+Alchemist)
  svd_fig34          Figs 3-4 (rank-20 truncated SVD + overhead split)
  transfer_tables23  Tables 2-3 (tall-skinny vs short-wide transfers)
  overlap_async      beyond-paper: sync vs pipelined task-queue engine,
                     relayout plan-cache hit rate (DESIGN.md §3/§5)
  offload_plan       beyond-paper: naive round-trip vs lazy-planned offload
                     (bytes over the bridge + elided crossings, DESIGN.md §6)
  spill_pressure     beyond-paper: memory governor with a working set ≥2× the
                     HBM budget — spill/refill counters, bounded high water,
                     padded uneven-shape sends (DESIGN.md §7)
  cross_session      beyond-paper: engine-level resident store + v2 admission
                     — a second session is *queued* for admission (DESIGN.md
                     §9), then its identical dataset attaches with zero
                     bridge bytes; two sessions 2× overcommitted against one
                     shared HBM budget stay bounded + bit-exact (DESIGN.md §8)
  overlap_spill      beyond-paper: asynchronous data plane — spill copy-outs
                     on the transfer ring overlapped with queue-worker
                     compute, measured as an overlap ratio and compared
                     bit-exactly against the synchronous baseline
                     (DESIGN.md §10)
  wire_overhead      beyond-paper: TCP transport vs loopback — framing
                     overhead over the raw matrix bytes and engine-side
                     bridge-counter parity (DESIGN.md §11)
  wire_throughput    beyond-paper: v2 streaming wire data plane — bit-exact
                     multi-shard TCP round trips with zero full-array
                     reassembly on receive, device_put/socket overlap ratio,
                     multi-in-flight depth, vectored-write counts
                     (DESIGN.md §13)
  admission_fairness beyond-paper: unified placement scheduler — a large
                     ticket under a small-connect storm is passed at most
                     ``aging_bound`` times (p50/p95 ticket waits reported),
                     and a content-affine reader joins the writer's shared
                     worker group with zero engine-side attach bytes
                     (DESIGN.md §12)
  fleet_recovery     beyond-paper: fleet chaos gate — kill one engine of a
                     2-engine supervised fleet mid-pipeline; the survivor
                     replays the lost DAG suffix bit-identically, refills
                     residents by content key with zero re-sent bytes, and
                     the replay is bounded by the analytically-priced lost
                     suffix (DESIGN.md §14)

Prints ``name,us_per_call,derived`` CSV rows. ``--only`` takes a
comma-separated subset; ``--json PATH`` additionally writes the structured
metrics each suite records — each suite block carries a ``runtime`` config
record (allocator, XLA flags, device count; repro.launch.runtime) so a
regression is attributable to environment drift, plus the merged
``engine.stats()`` snapshot that cross_session embeds — the file CI uploads
as ``BENCH_ci.json`` and gates against ``benchmarks/BENCH_baseline.json``
(see check_regression.py). ``--tuned`` re-execs the process under the tuned
runtime recipe (tcmalloc LD_PRELOAD when installed, emulated device count,
32-bit dtype defaults) before any jax import binds the environment.

    PYTHONPATH=src python -m benchmarks.run [--only offload,spill] \
        [--tuned] [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List

SUITE_NAMES = [
    "gemm", "svd", "transfer", "overlap", "offload", "spill", "cross",
    "overlap_spill", "wire", "wire_throughput", "admission", "fleet",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default=None,
        help=f"comma-separated subset of: {','.join(SUITE_NAMES)}",
    )
    ap.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="also write structured per-suite metrics as JSON",
    )
    ap.add_argument(
        "--tuned",
        action="store_true",
        help="re-exec under the tuned runtime recipe (repro.launch.runtime)",
    )
    args = ap.parse_args()

    if args.tuned:
        # Before any benchmark import pulls in jax: LD_PRELOAD and XLA flags
        # bind at process start, so the only honest application is a re-exec
        # (a no-op if this process is already the tuned one).
        from repro.launch import runtime

        runtime.ensure_tuned()

    from benchmarks import (
        admission_fairness,
        cross_session,
        fleet_recovery,
        gemm_table1,
        offload_plan,
        overlap_async,
        overlap_spill,
        spill_pressure,
        svd_fig34,
        transfer_tables23,
        wire_overhead,
        wire_throughput,
    )
    from repro.launch import runtime

    runtime.enable_compile_cache()
    suites = {
        "gemm": gemm_table1.run,
        "svd": svd_fig34.run,
        "transfer": transfer_tables23.run,
        "overlap": overlap_async.run,
        "offload": offload_plan.run,
        "spill": spill_pressure.run,
        "cross": cross_session.run,
        "overlap_spill": overlap_spill.run,
        "wire": wire_overhead.run,
        "wire_throughput": wire_throughput.run,
        "admission": admission_fairness.run,
        "fleet": fleet_recovery.run,
    }

    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        unknown = [n for n in names if n not in suites]
        if unknown:
            ap.error(f"unknown suite(s) {unknown}; choose from {sorted(suites)}")
        suites = {n: suites[n] for n in names}

    report: List[str] = ["name,us_per_call,derived"]
    metrics: Dict[str, Dict] = {}
    t0 = time.perf_counter()
    for name, fn in suites.items():
        sys.stderr.write(f"[benchmarks] running {name} ...\n")
        fn(report, metrics)
    sys.stderr.write(f"[benchmarks] done in {time.perf_counter()-t0:.1f}s\n")
    print("\n".join(report))
    if args.json:
        # Every suite's block records the runtime it actually ran under —
        # regressions must be attributable to environment drift (allocator,
        # device count, flags), not guessed at.
        rt = runtime.snapshot()
        for block in metrics.values():
            block["runtime"] = rt
        with open(args.json, "w") as f:
            json.dump(metrics, f, indent=2, sort_keys=True)
        sys.stderr.write(f"[benchmarks] metrics written to {args.json}\n")


if __name__ == "__main__":
    main()
