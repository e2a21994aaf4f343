"""Communication-avoiding tall-skinny QR (TSQR) on the device mesh.

Ref: ml-matrix `TSQR.qrR` / `TSQR.solveLeastSquares` — local QR per
partition, tree-reduce of R factors via `treeAggregate` (SURVEY.md §2.2,
§3.2) [unverified]. TPU lowering (PAPERS.md arXiv:2112.09017): each shard
QRs its local block, `all_gather`s the small R factors over ICI, and every
chip reduces the stacked Rs with one more QR — replicated, so no driver hop.

The torus all-gather is the compiler-scheduled analog of the reference's
log-depth aggregation tree.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.scipy.linalg import solve_triangular
from jax.sharding import Mesh, PartitionSpec as P

from keystone_tpu.config import config
from keystone_tpu.linalg.row_matrix import RowMatrix


@lru_cache(maxsize=None)
def _tsqr_r_fn(mesh: Mesh, axis: str):
    # check_vma=False: the all_gather makes the value replicated, but the
    # static replication checker can't see through the second QR.
    @jax.jit
    @partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False
    )
    def tsqr_r(a):  # a: (m_local, d)
        d = a.shape[1]
        r = jnp.linalg.qr(a, mode="r")  # (min(m_local, d), d)
        if r.shape[0] < d:  # static shapes: pad so all_gather stacks cleanly
            r = jnp.pad(r, ((0, d - r.shape[0]), (0, 0)))
        rs = lax.all_gather(r, axis)  # (shards, d, d)
        return jnp.linalg.qr(rs.reshape(-1, d), mode="r")  # (d, d)

    return tsqr_r


def tsqr_r(A: RowMatrix) -> jax.Array:
    """The R factor of A's QR decomposition, replicated. R is unique up to
    row signs; RᵀR == AᵀA regardless."""
    return _tsqr_r_fn(A.mesh, config.data_axis)(A.data)


@partial(jax.jit, static_argnames=("d",))
def _solve_from_augmented_r(r_aug, d: int, lam):
    """Given R of [A | B] and ridge lam, solve min ||AW-B||² + lam||W||².

    R11 = R[:d, :d], R12 = R[:d, d:]. Ridge: stack sqrt(lam)·I under R11
    (equivalent to appending those rows to A) and re-QR the small system.
    """
    k = r_aug.shape[1] - d
    dtype = r_aug.dtype
    sq = jnp.sqrt(lam)
    top = r_aug[:d]  # [R11 | R12]
    bot = jnp.concatenate(
        [sq * jnp.eye(d, dtype=dtype), jnp.zeros((d, k), dtype=dtype)], axis=1
    )
    rr = jnp.linalg.qr(jnp.concatenate([top, bot], axis=0), mode="r")
    return solve_triangular(rr[:d, :d], rr[:d, d : d + k])


def solve_least_squares_tsqr(
    A: RowMatrix, B: RowMatrix, lam: float = 0.0
) -> jax.Array:
    """Least squares through TSQR of the augmented [A | B] — numerically
    stabler than normal equations (condition κ instead of κ²), the same
    reason the reference offers TSQR next to NormalEquations."""
    A._check_aligned(B)
    d = A.data.shape[1]
    aug = RowMatrix(
        jnp.concatenate([A.data, B.data.astype(A.data.dtype)], axis=1),
        A.n,
        A.mesh,
    )
    r_aug = tsqr_r(aug)
    return _solve_from_augmented_r(
        r_aug, d, jnp.asarray(lam, dtype=r_aug.dtype)
    )
