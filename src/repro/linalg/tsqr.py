"""Communication-avoiding tall-skinny QR (TSQR).

The workhorse for orthogonalization of tall-skinny blocks (randomized SVD's
range finder, Lanczos restarts). Rows are sharded 1D over all mesh axes
(the ROW layout); each device QRs its slab, the small R factors are combined
in a single gather (or a binary tree for large device counts), and the local
Q factors are corrected.

Cost: one all-gather of [n x n] factors — independent of m. This is the
TPU analogue of the MPI TSQR in communication-avoiding linear algebra, and
is exactly the kind of routine the paper offloads.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.core.layouts import ROW


def _all_axes(mesh: Mesh):
    return tuple(mesh.axis_names)


def _num_devices(mesh: Mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n


def tsqr(a: jax.Array, mesh: Mesh, *, tree: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Reduced QR of a tall-skinny ROW-layout matrix.

    Returns (Q [m, n] ROW layout, R [n, n] replicated). Requires m >= n per
    device slab after padding (tall-skinny contract).
    """
    m, n = a.shape
    p = _num_devices(mesh)
    axes = _all_axes(mesh)

    pad = (-m) % p
    a_p = jnp.pad(a, ((0, pad), (0, 0))) if pad else a
    if a_p.shape[0] // p < n:
        # Not enough rows per shard to be "tall" — fall back to replicated QR.
        q, r = jnp.linalg.qr(a_p, mode="reduced")
        return q[:m], r

    spec = ROW.partition_spec(mesh)
    a_p = jax.lax.with_sharding_constraint(a_p, NamedSharding(mesh, spec))

    def local(a_loc: jax.Array) -> Tuple[jax.Array, jax.Array]:
        q1, r1 = jnp.linalg.qr(a_loc, mode="reduced")  # [m/p, n], [n, n]
        if p == 1:
            return q1, r1
        if tree:
            q_corr, r_final = _tree_combine(r1, axes, p)
        else:
            # one-shot: gather all R factors, QR the [p*n, n] stack everywhere
            rs = jax.lax.all_gather(r1, axes, axis=0, tiled=True)  # [p*n, n]
            q2, r_final = jnp.linalg.qr(rs, mode="reduced")        # [p*n, n]
            rank = _flat_rank(axes)
            q_corr = jax.lax.dynamic_slice_in_dim(q2, rank * n, n, axis=0)
        q = q1 @ q_corr
        # Sign-fix: make R's diagonal non-negative for determinism.
        sign = jnp.sign(jnp.where(jnp.diag(r_final) == 0, 1.0, jnp.diag(r_final)))
        return q * sign[None, :], r_final * sign[:, None]

    def _flat_rank(axis_names):
        # Axis sizes come from the (statically known) mesh: they are
        # compile-time constants.
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        rank = jax.lax.axis_index(axis_names[0])
        for ax in axis_names[1:]:
            rank = rank * sizes[ax] + jax.lax.axis_index(ax)
        return rank

    def _tree_combine(r1, axis_names, nproc):
        """Binary-tree R combination via ppermute (log2 p rounds)."""
        if nproc & (nproc - 1):
            raise ValueError(f"tree TSQR needs a power-of-two device count, got {nproc}")
        rank = _flat_rank(axis_names)
        q_corr = jnp.eye(r1.shape[0], dtype=r1.dtype)
        r_cur = r1
        step = 1
        while step < nproc:
            # partner exchange: lower of each pair stacks [r_self; r_partner]
            perm_down = [(i, i ^ step) for i in range(nproc)]
            r_other = _ppermute_all(r_cur, axis_names, perm_down)
            is_low = (rank & step) == 0
            # stack in a fixed order: low rank's R on top
            r_top = jnp.where(is_low, r_cur, r_other)
            r_bot = jnp.where(is_low, r_other, r_cur)
            q2, r_new = jnp.linalg.qr(jnp.concatenate([r_top, r_bot], axis=0), mode="reduced")
            n_ = r1.shape[0]
            block = jnp.where(is_low, q2[:n_], q2[n_:])
            q_corr = q_corr @ block
            r_cur = r_new
            step *= 2
        return q_corr, r_cur

    def _ppermute_all(x, axis_names, perm):
        # ppermute over the flattened axes: express as a single permutation
        # over the lexicographic rank by permuting each axis jointly.
        return jax.lax.ppermute(x, axis_names, perm)

    q, r_rep = jax.shard_map(
        lambda a_loc: local(a_loc),
        mesh=mesh,
        in_specs=(spec,),
        out_specs=(spec, jax.sharding.PartitionSpec(None, None)),
        # R is replicated by construction (gathered QR)
        check_vma=False,
    )(a_p)
    return q[:m], r_rep
