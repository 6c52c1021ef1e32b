"""Problems: how a configuration's operands are made from the seed, which
routine the served path runs on them, and the plain reference that decides
whether what came back is correct.

A configuration file names its problem (``"problem": "lowrank_svd"``); the
module of that name here exposes ``build(cfg, seed) -> Problem``. A Problem has

- ``operands``: the host arrays the client sends, by name;
- ``routine``: a :class:`Routine`, what the job runs on them;
- ``fresh(job)``: the operands of job ``job`` where the traffic sends fresh
  ones per job, each with a stamp of its own (a problem that cannot be
  stamped raises);
- ``drop_operands()``: called once resident operands are sent, to drop what
  the reference does not need;
- ``retain(job, collected)``: what the check needs of one job's collected
  values, kept once the job has ended;
- ``check(retained) -> dict``: readings by name, the worst over the jobs;
- ``control(job, collected_kind, precision)``: the reference computed at
  ``precision`` in the program's place, collected as the program's values
  would be (the configuration names, as ``control``, the step below its own).

The references import nothing of the program and take nothing it made.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, field

import numpy as np


@dataclass(frozen=True)
class Routine:
    library: str
    name: str
    args: tuple  # operand names, in call order
    outputs: tuple  # output names, in return order
    params: dict = field(default_factory=dict)


def load(name: str):
    """The problem module ``bench.problems.<name>``."""
    return importlib.import_module(f"{__name__}.{name}")


def rng(seed: int, *keys: int) -> np.random.Generator:
    """A generator for (seed, *keys): any whole seed, negative or past 64 bits."""
    return np.random.default_rng([seed % (1 << 64), *keys])


def jax_key(seed: int, stream: int):
    """A JAX PRNG key drawn from (seed, stream), for operands made on the device."""
    import jax

    return jax.random.PRNGKey(int(rng(seed, stream).integers(0, 1 << 31)))


def worst(readings: list[dict]) -> dict:
    """The largest reading of each name over the jobs checked."""
    out: dict = {}
    for r in readings:
        for key, val in r.items():
            out[key] = max(out.get(key, val), val)
    return out
