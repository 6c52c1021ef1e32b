"""The truncated SVD's least time (one read of A and the writes of U, s and V,
or 2 m n k FLOPs at the bf16 peak, whichever is longer) over the device's
busy time per job in the traced window, per chip on several chips."""

from bench import roofline


def read(run):
    t, cfg = run.trace, run.cell.config
    if t is None or not run.jobs or t.busy_s <= 0:
        return None
    flops, nbytes = roofline.truncated_svd_counts(cfg["m"], cfg["n"], cfg["k"])
    return roofline.share_pct(flops, nbytes, run.device_kind, t.busy_s / run.jobs, run.cell.chips)
