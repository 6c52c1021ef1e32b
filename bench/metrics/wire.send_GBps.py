"""Bytes sent over time spent sending, summed over the window's sends: from the
send call until the operand is resident as the client sees it (the eager
policy returns then), through snapshot, content key, framing and placement."""


def read(run):
    spans = [s for s in run.spans if s.verb == "send"]
    seconds = sum(s.t1 - s.t0 for s in spans)
    return sum(s.nbytes for s in spans) / seconds / 1e9 if spans and seconds > 0 else None
