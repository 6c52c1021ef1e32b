"""Share of each chip's busy time in which a collective was in flight (an
async pair from its start to its done, where the chip ran some op), averaged
over the chips of the traced window; at most 100 by construction."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.collective_s / t.busy_s
