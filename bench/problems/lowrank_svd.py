"""Rank-k truncated SVD of a dense m x n matrix with a known spectrum.

A = Us Vt with Us = Qu diag(sigma) and Vt = Qv^T, Qu [m, r] and Qv [n, r]
orthonormal (float64 QR, then stored as float32). sigma is k, k-1, ..., 1 (a
gap of 1 between the values asked for), then a tail decaying from 0.1, so
the top k are well separated from the rest.

The reference is exact for the product of the stored float32 factors: its
SVD comes from the QR of each factor and the SVD of an r x r matrix, in
float64, and it multiplies by A in that factored form. The matrix the client
sends is that product rounded once to float32 (computed on the device at
HIGHEST precision), so it differs from the reference's A by one float32
rounding of each element, some 1e-10 in sigma: far below every limit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.problems import Routine, jax_key, rng, worst
from bench.problems.precision import rounder

BLOCK_ROWS = 8192  # rows of A made on the device per call


class Problem:
    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        m, n, k = cfg["m"], cfg["n"], cfg["k"]
        r = min(k + cfg["tail"], m, n)
        self.k = k
        self.sigma = np.concatenate(
            [np.arange(k, 0, -1, dtype=np.float64), 0.1 * 0.8 ** np.arange(r - k)]
        )
        g = rng(seed, 0)
        self.us = (np.linalg.qr(g.standard_normal((m, r)))[0] * self.sigma).astype(np.float32)
        self.vt = np.linalg.qr(g.standard_normal((n, r)))[0].T.astype(np.float32)
        self.operands = {"A": product_on_device(self.us, self.vt, devices(cfg))}
        self.routine = Routine(
            library="elemental",
            name="truncated_svd",
            args=("A",),
            outputs=("U", "s", "V"),
            params={"k": k, "oversample": cfg["oversample"]},
        )
        self._exact = None

    def fresh(self, job: int) -> dict:
        raise NotImplementedError("a known spectrum cannot be stamped per job")

    def retain(self, job: int, collected: dict):
        return tuple(np.asarray(collected[name]) for name in self.routine.outputs)

    def exact(self) -> "Exact":
        if self._exact is None:
            self._exact = Exact(self.us, self.vt, self.k)
        return self._exact

    def drop_operands(self) -> None:
        """Drop the client's copy of A once it is resident: the reference needs
        only the factors, and the host holds several copies while it sends."""
        self.operands = {}

    def check(self, retained: list) -> dict:
        ref = self.exact()
        return worst([readings(ref, u, s, v) for _, (u, s, v) in retained])

    def control(self, job: int, collected_kind: str, precision: str) -> dict:
        u, s, v = control_svd(self, precision)
        return {"U": u, "s": s, "V": v}


def build(cfg: dict, seed: int) -> Problem:
    return Problem(cfg, seed)


def devices(cfg: dict) -> list:
    """The chips of the configuration's grid."""
    return jax.devices()[: math.prod(cfg["grid"])]


def product_on_device(us: np.ndarray, vt: np.ndarray, chips: list) -> np.ndarray:
    """fl32(us @ vt) on the host, made in row blocks, one block on each chip
    at a time, fetched together."""
    m, r = us.shape
    out = np.empty((m, vt.shape[1]), np.float32)
    rows = min(BLOCK_ROWS, m)
    pad = np.zeros((-m % rows, r), np.float32)
    us_p = np.concatenate([us, pad]) if len(pad) else us
    vts = [jax.device_put(vt, d) for d in chips]
    starts = list(range(0, m, rows))
    for i in range(0, len(starts), len(chips)):
        group = starts[i : i + len(chips)]
        blocks = [
            _block(jax.device_put(us_p[lo : lo + rows], d), v)
            for lo, d, v in zip(group, chips, vts)
        ]
        for lo, block in zip(group, jax.device_get(blocks)):
            out[lo : lo + rows] = block[: m - lo]
    return out


@jax.jit
def _block(u, v):
    return jnp.dot(u, v, precision=jax.lax.Precision.HIGHEST)


class Exact:
    """The SVD of A = us @ vt in float64, and products with A in factored form."""

    def __init__(self, us: np.ndarray, vt: np.ndarray, k: int):
        self.us, self.vt = us.astype(np.float64), vt.astype(np.float64)
        qu, ru = np.linalg.qr(self.us)
        qv, rv = np.linalg.qr(self.vt.T)
        w, sig, zt = np.linalg.svd(ru @ rv.T)
        self.sigma = sig[:k]
        self.u = qu @ w[:, :k]
        self.v = qv @ zt.T[:, :k]

    def av(self, x: np.ndarray) -> np.ndarray:
        return self.us @ (self.vt @ x)

    def atu(self, y: np.ndarray) -> np.ndarray:
        return self.vt.T @ (self.us.T @ y)


def readings(ref: Exact, u, s, v) -> dict:
    """Each number compared, for one computed (U, s, V), in float64.

    - ``sigma_rel_err``: max over i of |s_i - sigma_i| / sigma_i;
    - ``residual_rel``: max over i of ||A v_i - s_i u_i|| and ||A^T u_i - s_i v_i||,
      over sigma_1;
    - ``ortho``: max deviation of U^T U and V^T V from the identity;
    - ``vector_err``: max over i of the distance of u_i and v_i from the exact
      singular vectors, each sign matched;
    - ``malformed``: 1 where a shape is wrong or a value is not finite.
    """
    k = len(ref.sigma)
    u, s, v = (np.asarray(x, np.float64) for x in (u, s, v))
    m, n = ref.us.shape[0], ref.vt.shape[1]
    if (
        u.shape != (m, k)
        or s.shape != (k,)
        or v.shape != (n, k)
        or not (np.isfinite(u).all() and np.isfinite(s).all() and np.isfinite(v).all())
    ):
        return {"malformed": 1.0}
    res_av = np.linalg.norm(ref.av(v) - u * s, axis=0)
    res_atu = np.linalg.norm(ref.atu(u) - v * s, axis=0)
    eye = np.eye(k)

    def dist(x, y):
        sign = np.where(np.sum(x * y, axis=0) < 0, -1.0, 1.0)
        return np.linalg.norm(x * sign - y, axis=0).max()

    return {
        "malformed": 0.0,
        "sigma_rel_err": float((np.abs(s - ref.sigma) / ref.sigma).max()),
        "residual_rel": float(max(res_av.max(), res_atu.max()) / ref.sigma[0]),
        "ortho": float(max(np.abs(u.T @ u - eye).max(), np.abs(v.T @ v - eye).max())),
        "vector_err": float(max(dist(u, ref.u), dist(v, ref.v))),
    }


# -- the plain reference, one precision step down -------------------------------
def control_svd(problem: Problem, precision: str):
    """Golub-Kahan-Lanczos with full reorthogonalisation, written plainly, with
    every operand of every product rounded to ``precision`` (see
    :mod:`bench.problems.precision`) and float32 accumulation.

    A is made on the configuration's chips with its rows split evenly over
    them, so a matrix that no one chip holds fits; the products are then
    summed across chips as XLA partitions them."""
    cfg = problem.cfg
    steps = min(cfg["k"] + cfg["oversample"], cfg["m"], cfg["n"])
    a = rows_on(devices(cfg), problem.us, problem.vt)
    aq = _round_rows(a, precision=precision)  # A's buffer becomes the rounded copy
    out = _gkl(aq, jax_key(problem.seed, 1), k=cfg["k"], steps=steps, precision=precision)
    del a, aq
    return tuple(np.asarray(x) for x in out)


def rows_on(chips: list, us: np.ndarray, vt: np.ndarray):
    """fl32(us @ vt) on ``chips``, its rows split evenly over them."""
    if len(us) % len(chips):
        raise ValueError(f"{len(us)} rows do not split evenly over {len(chips)} chips")
    mesh = jax.sharding.Mesh(np.array(chips), ("rows",))
    rows = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("rows", None))
    whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.jit(_block, out_shardings=rows)(
        jax.device_put(us, rows), jax.device_put(vt, whole)
    )


@functools.partial(jax.jit, static_argnames=("precision",), donate_argnums=0)
def _round_rows(a, *, precision: str):
    """A stored once at ``precision``, per-row scaled, as a program would keep it."""
    return rounder(precision)[0](a, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "steps", "precision"))
def _gkl(aq, key, *, k: int, steps: int, precision: str):
    rnd, prec = rounder(precision)
    dot = functools.partial(jnp.dot, precision=prec, preferred_element_type=jnp.float32)
    m, n = aq.shape

    def reorth(x, basis, valid):
        for _ in range(2):
            x = x - dot(rnd(basis.T), rnd(dot(rnd(basis), rnd(x)) * valid))
        return x

    def step(carry, i):
        v, u_prev, beta_prev, us, vs = carry
        u = dot(aq, rnd(v)) - beta_prev * u_prev
        u = reorth(u, us, (jnp.arange(steps) < i).astype(jnp.float32))
        alpha = jnp.linalg.norm(u)
        u = u / alpha
        vs = vs.at[i].set(v)
        w = dot(aq.T, rnd(u)) - alpha * v
        w = reorth(w, vs, (jnp.arange(steps) <= i).astype(jnp.float32))
        beta = jnp.linalg.norm(w)
        return (w / beta, u, beta, us.at[i].set(u), vs), (alpha, beta)

    v0 = jax.random.normal(key, (n,), jnp.float32)
    carry = (
        v0 / jnp.linalg.norm(v0),
        jnp.zeros((m,), jnp.float32),
        jnp.float32(0),
        jnp.zeros((steps, m), jnp.float32),
        jnp.zeros((steps, n), jnp.float32),
    )
    (_, _, _, us, vs), (alphas, betas) = jax.lax.scan(step, carry, jnp.arange(steps))
    ub, s, vbt = jnp.linalg.svd(jnp.diag(alphas) + jnp.diag(betas[:-1], k=1))
    return dot(rnd(us.T), rnd(ub[:, :k])), s[:k], dot(rnd(vs.T), rnd(vbt.T[:, :k]))
