"""Set-up: process start to the first timed job (host clock). It covers JAX's
start, building the cell's operands from the seed, sending the resident ones,
and the warm jobs that compile or load every program the window runs."""


def read(run):
    return run.setup_s
