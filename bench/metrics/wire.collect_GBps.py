"""Bytes collected over time spent collecting, summed over the window's
.data() calls that brought back an array (FETCH through the wire)."""


def read(run):
    spans = [s for s in run.spans if s.verb == "collect" and s.nbytes > 0]
    seconds = sum(s.t1 - s.t0 for s in spans)
    return sum(s.nbytes for s in spans) / seconds / 1e9 if spans and seconds > 0 else None
