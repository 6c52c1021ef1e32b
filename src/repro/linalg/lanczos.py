"""Golub–Kahan–Lanczos bidiagonalization — the ARPACK analogue.

The paper's truncated SVD (§4.2) is "our own MPI-based implementation of the
truncated SVD using ARPACK and Elemental": ARPACK runs the (implicitly
restarted) Lanczos iteration, Elemental supplies the distributed matvec.

Here the same split: this module runs Golub–Kahan–Lanczos with full
reorthogonalization as a ``lax.scan`` (jit-friendly, fixed iteration count =
k + oversampling, the practical equivalent of ARPACK's Krylov subspace
dimension ``ncv``), while the distributed matvecs ``A v`` / ``Aᵀ u`` run
under GRID sharding constraints so XLA partitions them across the worker
grid. The small bidiagonal SVD happens replicated ("on the driver").

bf16 note (DESIGN.md §2): Krylov vectors and reorthogonalization run f32 —
bf16 Gram updates destroy orthogonality within a few iterations.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import sharding as shardcore
from repro.core.layouts import GRID


class BidiagState(NamedTuple):
    u: jax.Array       # [m] current left vector
    v: jax.Array       # [n] current right vector
    alpha: jax.Array   # [] current diagonal entry
    beta: jax.Array    # [] current superdiagonal entry
    us: jax.Array      # [L, m] left Krylov basis
    vs: jax.Array      # [L, n] right Krylov basis


def _reorth(x: jax.Array, basis: jax.Array, valid: jax.Array) -> jax.Array:
    """Two-pass classical Gram–Schmidt against rows of ``basis`` (masked)."""
    for _ in range(2):
        coeff = (basis @ x) * valid          # [L]
        x = x - basis.T @ coeff
    return x


def bidiagonalize(
    a: jax.Array,
    num_iters: int,
    *,
    mesh: Optional[Mesh] = None,
    seed: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Run ``num_iters`` GKL steps on A [m, n].

    Returns (U [L, m], V [L, n], alphas [L], betas [L]) with
    A ≈ Uᵀ B V where B = bidiag(alphas, betas[1:]).
    """
    m, n = a.shape
    L = num_iters
    a32 = a.astype(jnp.float32)
    if mesh is not None:
        a32 = shardcore.constrain(a32, GRID.partition_spec(mesh), mesh)

    key = jax.random.PRNGKey(seed)
    v0 = jax.random.normal(key, (n,), jnp.float32)
    v0 = v0 / jnp.linalg.norm(v0)

    def step(carry, i):
        v, u_prev, beta_prev, us, vs = carry
        # u_i = A v_i - beta_i u_{i-1}
        with jax.named_scope("lanczos.matvec"):
            u = a32 @ v - beta_prev * u_prev
        valid_u = (jnp.arange(L) < i).astype(jnp.float32)
        with jax.named_scope("lanczos.reorth"):
            u = _reorth(u, us, valid_u)
        alpha = jnp.linalg.norm(u)
        u = u / jnp.where(alpha > 1e-12, alpha, 1.0)

        # v_{i+1} = Aᵀ u_i - alpha_i v_i
        with jax.named_scope("lanczos.rmatvec"):
            w = a32.T @ u - alpha * v
        vs_i = vs.at[i].set(v)
        valid_v = (jnp.arange(L) <= i).astype(jnp.float32)
        with jax.named_scope("lanczos.reorth"):
            w = _reorth(w, vs_i, valid_v)
        beta = jnp.linalg.norm(w)
        v_next = w / jnp.where(beta > 1e-12, beta, 1.0)

        us_i = us.at[i].set(u)
        return (v_next, u, beta, us_i, vs_i), (alpha, beta)

    us0 = jnp.zeros((L, m), jnp.float32)
    vs0 = jnp.zeros((L, n), jnp.float32)
    carry0 = (v0, jnp.zeros((m,), jnp.float32), jnp.float32(0.0), us0, vs0)
    (v_last, u_last, beta_last, us, vs), (alphas, betas) = jax.lax.scan(
        step, carry0, jnp.arange(L)
    )
    return us, vs, alphas, betas


def truncated_svd_lanczos(
    a: jax.Array,
    k: int,
    *,
    oversample: int = 10,
    mesh: Optional[Mesh] = None,
    seed: int = 0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Rank-k truncated SVD via GKL bidiagonalization.

    Returns (U [m, k], s [k], V [n, k]). ``k + oversample`` plays ARPACK's
    ``ncv`` role; the bidiagonal system is solved replicated, mirroring
    ARPACK-on-the-driver in MLlib/the paper's MPI code.
    """
    m, n = a.shape
    L = min(k + oversample, min(m, n))
    us, vs, alphas, betas = bidiagonalize(a, L, mesh=mesh, seed=seed)

    # GKL recurrence as implemented above:
    #   u_i = (A v_i - beta_{i-1} u_{i-1}) / alpha_i
    #     =>  A v_i  = alpha_i u_i + beta_{i-1} u_{i-1}
    #   v_{i+1} = (Aᵀ u_i - alpha_i v_i) / beta_i
    #     =>  Aᵀ u_i = alpha_i v_i + beta_i v_{i+1}
    # so A V = U B with upper-bidiagonal B: B[i,i] = alpha_i,
    # B[j,j+1] = beta_j.
    b_small = jnp.diag(alphas) + jnp.diag(betas[:-1], k=1)

    with jax.named_scope("lanczos.small_svd"):
        ub, s, vbt = jnp.linalg.svd(b_small, full_matrices=False)
    with jax.named_scope("lanczos.basis"):
        u_out = us.T @ ub[:, :k]  # [m, k]
        v_out = vs.T @ vbt.T[:, :k]  # [n, k]
    return u_out.astype(a.dtype), s[:k].astype(a.dtype), v_out.astype(a.dtype)
