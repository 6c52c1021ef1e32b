"""Answers altered where the library produces them: the faults the check has
to catch, by the routine that produces the answer.

The control test plants each in the library and sees ``correct`` come out
false; ``bench/calibrate.py`` applies each to the answers the program kept,
which reads them at a cell's own size as the planted fault would.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def _swap_u(usv):
    u, s, v = usv
    return u.at[:, [0, 1]].set(u[:, [1, 0]]), s, v


def _half_v(usv):
    u, s, v = usv
    return u, s, v.at[: v.shape[0] // 2, 2].multiply(-1.0)


FAULTS = {
    "gemm": {"c_column": lambda c: c.at[:, 0].multiply(1.5)},
    "truncated_svd": {
        "sigma_1": lambda usv: (usv[0], usv[1].at[0].multiply(1.01), usv[2]),
        "u_columns_swapped": _swap_u,
        "v_half_column_negated": _half_v,
    },
}


def alter(retained, fault):
    """What the check keeps of one job, as it would be had the library produced
    the answer altered by ``fault``: sampled rows of C, or (U, s, V)."""
    if isinstance(retained[0], str):
        kind, rows = retained
        return kind, np.asarray(fault(jnp.asarray(rows)))
    return tuple(np.asarray(x) for x in fault(tuple(jnp.asarray(x) for x in retained)))
