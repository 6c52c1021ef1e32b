"""Window length over jobs completed: one client in a closed loop starts jobs
until the run's seconds have passed, and the window ends when the job then in
flight completes (host clock)."""


def read(run):
    return run.window_s / run.jobs if run.jobs else None
