"""The GEMM's least time (2 m k n FLOPs at the bf16 peak, or A and B read and C
written once at HBM bandwidth, whichever is longer) over the device's busy
time per job in the traced window."""

from bench import roofline


def read(run):
    t, cfg = run.trace, run.cell.config
    if t is None or not run.jobs or t.busy_s <= 0:
        return None
    flops, nbytes = roofline.gemm_counts(cfg["m"], cfg["k"], cfg["n"])
    return roofline.share_pct(flops, nbytes, run.device_kind, t.busy_s / run.jobs, run.cell.chips)
