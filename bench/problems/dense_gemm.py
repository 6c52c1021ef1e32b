"""Dense C = A @ B with seeded standard-normal float32 entries.

The reference is float64 NumPy over the very float32 operands the client
sent: a seeded sample of C's rows, elementwise and normwise, or the
Frobenius norm of the whole product, accumulated over row blocks. Where the
traffic sends fresh operands per job, each job's A and B carry a stamp, one
element of each set from (seed, job), and the reference uses the stamped
values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.problems import Routine, jax_key, rng, worst
from bench.problems.precision import rounder

U_BF16 = 2.0**-9  # unit roundoff of bfloat16
U_F32 = 2.0**-24  # unit roundoff of float32
NORM_BLOCK = 1024  # rows of A per float64 block of the Frobenius reference


class Problem:
    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        m, k, n = cfg["m"], cfg["k"], cfg["n"]
        self.operands = {
            "A": np.array(_normal(jax_key(seed, 0), (m, k))),  # writable: fresh() stamps it
            "B": np.array(_normal(jax_key(seed, 1), (k, n))),
        }
        self.corner = {name: float(x[0, 0]) for name, x in self.operands.items()}
        self.stamps: dict = {}  # job -> {operand: value at [0, 0]}
        self.routine = Routine(library="elemental", name="gemm", args=("A", "B"), outputs=("C",))
        self._b64 = self._abs_b64 = None
        self._fro = None

    # -- operands ----------------------------------------------------------------
    def fresh(self, job: int) -> dict:
        """Job ``job``'s operands: the templates with [0, 0] of each stamped in
        place, so that no two jobs send the same bytes."""
        vals = rng(self.seed, 2, job).standard_normal(len(self.operands)).astype(np.float32)
        self.stamps[job] = dict(zip(self.operands, (float(v) for v in vals)))
        for name, x in self.operands.items():
            x[0, 0] = self.stamps[job][name]
        return self.operands

    def _corner(self, job: int, name: str) -> float:
        return self.stamps.get(job, self.corner)[name]

    def inputs(self, job: int) -> dict:
        """Copies of job ``job``'s operands, as they were sent."""
        out = {name: x.copy() for name, x in self.operands.items()}
        for name, x in out.items():
            x[0, 0] = self._corner(job, name)
        return out

    def drop_operands(self) -> None:
        """The reference reads the operands: the client keeps its copy."""

    # -- what the check keeps of a job ---------------------------------------------
    def rows(self, job: int) -> np.ndarray:
        m = self.cfg["m"]
        return np.sort(rng(self.seed, 3, job).choice(m, self.cfg["check_rows"], replace=False))

    def retain(self, job: int, collected: dict):
        if "C" in collected:
            return ("rows", np.asarray(collected["C"])[self.rows(job)].copy())
        return ("normest", float(np.asarray(collected["normest"])))

    # -- the reference ---------------------------------------------------------------
    def _b(self, job: int) -> tuple:
        """B of job ``job`` in float64, and its absolute values."""
        if self._b64 is None:
            self._b64 = self.operands["B"].astype(np.float64)
            self._abs_b64 = np.abs(self._b64)
        self._b64[0, 0] = self._corner(job, "B")
        self._abs_b64[0, 0] = abs(self._b64[0, 0])
        return self._b64, self._abs_b64

    def frobenius(self) -> float:
        """||A B||_F in float64 over the unstamped operands, by row blocks."""
        if self._fro is None:
            a, (b, _) = self.operands["A"], self._b(-1)
            total = 0.0
            for lo in range(0, a.shape[0], NORM_BLOCK):
                blk = a[lo : lo + NORM_BLOCK].astype(np.float64)
                if lo == 0:
                    blk[0, 0] = self.corner["A"]
                total += float(np.square(blk @ b).sum())
            self._fro = float(np.sqrt(total))
        return self._fro

    def job_readings(self, job: int, kept) -> dict:
        kind, val = kept
        if kind == "normest":
            if job in self.stamps:
                raise NotImplementedError("the Frobenius reference covers unstamped operands")
            if not np.isfinite(val):
                return {"malformed": 1.0}
            ref = self.frobenius()
            return {"malformed": 0.0, "normest_rel_err": abs(val - ref) / ref}
        rows = self.rows(job)
        got = np.asarray(val, np.float64)
        if got.shape != (len(rows), self.cfg["n"]) or not np.isfinite(got).all():
            return {"malformed": 1.0}
        a = self.operands["A"][rows].astype(np.float64)
        if rows[0] == 0:
            a[0, 0] = self._corner(job, "A")
        b, abs_b = self._b(job)
        ref = a @ b
        # One bf16 pass rounds both operands (2 u_bf16 per product); float32
        # accumulation of k products adds at most k u_f32, all relative to |A||B|.
        bound = (2 * U_BF16 + (self.cfg["k"] + 2) * U_F32) * (np.abs(a) @ abs_b)
        err = np.abs(got - ref)
        return {
            "malformed": 0.0,
            "rel_fro_err": float(np.linalg.norm(got - ref) / np.linalg.norm(ref)),
            "err_over_bound": float((err / bound).max()),
        }

    def check(self, retained: list) -> dict:
        return worst([self.job_readings(job, kept) for job, kept in retained])

    # -- the plain reference, one precision step down ---------------------------------
    def control(self, job: int, collected_kind: str, precision: str) -> dict:
        ops = self.inputs(job)
        c = _product(jnp.asarray(ops["A"]), jnp.asarray(ops["B"]), precision=precision)
        if collected_kind == "normest":
            return {"normest": float(jnp.linalg.norm(c))}
        return {"C": np.asarray(c)}


def build(cfg: dict, seed: int) -> Problem:
    return Problem(cfg, seed)


@functools.partial(jax.jit, static_argnames=("shape",))
def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnames=("precision",))
def _product(a, b, *, precision: str):
    """A @ B with each operand rounded to ``precision`` (A per row, B per
    column) and float32 accumulation."""
    rnd, prec = rounder(precision)
    return jnp.dot(rnd(a, axis=1), rnd(b, axis=0), precision=prec, preferred_element_type=jnp.float32)
