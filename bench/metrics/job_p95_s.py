"""The 95th percentile of the latency of every job in the window (host clock)."""

from bench.harness import p95


def read(run):
    return p95(run.latencies) if run.latencies else None
