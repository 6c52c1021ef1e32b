"""Mean host time of one run, collect (.data()) or free call in the traced
window: client, planner, TCP and the engine's task queue, per verb. Each
span's length less the part of it in which the device was busy, so that a
verb that waits for a routine on the device counts only the host's time."""

VERBS = ("bench.run", "bench.collect", "bench.free")


def read(run):
    t = run.trace
    n = sum(t.verb_n.get(v, 0) for v in VERBS) if t is not None else 0
    if not n:
        return None
    return 1e3 * sum(t.verb_host_s.get(v, 0.0) for v in VERBS) / n
