"""Kernel ridge regression by block Gauss-Seidel over kernel column blocks.

Ref: src/main/scala/nodes/learning/KernelRidgeRegression.scala,
KernelGenerator.scala, KernelBlockLinearMapper.scala [unverified]: the solver
of Tu et al., "Large Scale Kernel Learning using Block Coordinate Descent"
(arXiv:1602.05310). With α = 0 (n x k), for each epoch and each block B of
``block_size`` consecutive training rows in natural order:

    K_B   = k(X, X_B)                          (n x b)
    K_BB  = K_B[B, :]                          (b x b)
    R     = Y_B − K_Bᵀ α + K_BB α_B            (b x k)
    α_B  <- solve(K_BB + λ I, R)               (Cholesky)

TPU lowering: the block operand is a function of the n x d features, not an
array. K (n x n: 10 GB at CIFAR-10's 50,000 rows) is never an argument;
each visit's n x b block is generated from the row-sharded X inside one
program whose loop over all the epochs' visits is a ``lax.scan``: no host
round trip a block. Whether a block is kept for the later epochs or made
again is decided before the program is built, from sizes the code can see
(``_blocks_kept``: one shard's rows, the block, the epochs, the device's
reported memory). One epoch, or slots that do not fit, keep nothing: each
block is used and dropped, no more of K than one block ever live. Otherwise
the first epoch writes the blocks it generates into a store that lives and
dies inside the program, and the later epochs read them: upstream's
``cacheKernel``, without the flag. X_B is replicated for the visit (each
shard's rows of the block, ``psum``), K_Bᵀ α reduces over the sharded rows
(``sharded_rowsum``), and the b x b solve runs replicated on every chip. A
ragged last block and the pad rows of X are masked out of K; the pad
columns get 1 on the diagonal, so their α stays 0. X, Y, λ, the row count,
the visit order and the kernel's parameters are arguments of the program: a
second fit compiles nothing.

The matrix-free conjugate-gradient solver that bore this name is
``KernelRidgeCG`` (``kernel_ridge_cg.py``).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.scipy.linalg import cho_solve
from jax.sharding import Mesh, PartitionSpec as P

from keystone_tpu.config import config
from keystone_tpu.linalg.row_matrix import (
    RowMatrix,
    _precision,
    sharded_rowsum,
    solver_matmul,
)
from keystone_tpu.nodes.learning.kernels import KernelGenerator
from keystone_tpu.utils.mesh import fold_blocks
from keystone_tpu.utils.metrics import (
    active_tracer,
    device_hbm_bytes,
    device_scope,
    span_of,
)
from keystone_tpu.workflow import LabelEstimator, Transformer


class KernelBlockLinearMapper(Transformer):
    """scores(x) = k(x, X_train) @ α, a block of training rows at a time
    inside one program, so the test-kernel block never exceeds
    (batch, block) in memory. The last block is the last ``block`` rows,
    the ones an earlier block held masked out: no padded copy of X_train."""

    # The training rows, the dual weights and the kernel's parameters are
    # arguments of the program; the block size is its static part.
    array_fields = ("kernel", "X_train", "alpha")

    def __init__(self, kernel: KernelGenerator, X_train, alpha, block_size: int = 4096):
        self.kernel = kernel
        self.X_train = jnp.asarray(X_train)
        self.alpha = jnp.asarray(alpha)
        self.block_size = min(int(block_size), int(self.X_train.shape[0]))

    def apply_batch(self, X):
        n, b = self.X_train.shape[0], self.block_size

        def add_block(i, out):
            start = jnp.minimum(i * b, n - b)
            z = lax.dynamic_slice_in_dim(self.X_train, start, b)
            a = lax.dynamic_slice_in_dim(self.alpha, start, b)
            fresh = start + jnp.arange(b) >= i * b
            kb = jnp.where(fresh[None, :], self.kernel.block(X, z), 0.0)
            return out + jnp.matmul(kb, a, precision=lax.Precision.HIGHEST)

        out = jnp.zeros((X.shape[0], self.alpha.shape[1]), self.alpha.dtype)
        return lax.fori_loop(0, -(-n // b), add_block, out)

    def batch_call(self, X):
        n = int(self.X_train.shape[0])
        with span_of(active_tracer(), "krr.apply", "solver", rows=int(np.shape(X)[0]),
                     train_rows=n, blocks=-(-n // self.block_size)):
            return super().batch_call(X)


# ------------------------------------------------------- the block solver
#
# The three pieces of a visit that a planted fault replaces
# (benchmark/configs/cifar-random-patch-kernel-control.py): the order of the
# visits, the right-hand side, the diagonal.


def _visit_order(num_blocks: int, num_epochs: int) -> np.ndarray:
    """The block of every visit of the solve: natural order, every epoch.

    The only maker of the visits. The store of ``_block_solve_fn`` stands on
    its contract, which is upstream's permuter's too: the first
    ``num_blocks`` visits are one epoch, and no later visit goes to a block
    that epoch left out (``kernel_block_gauss_seidel`` keeps nothing for an
    order that breaks it)."""
    return np.tile(np.arange(num_blocks, dtype=np.int32), num_epochs)


def _block_residual(y_b, kt_alpha, k_bb, alpha_b, precision):
    """R = Y_B − K_Bᵀ α + K_BB α_B: the targets less what the other blocks
    explain."""
    return y_b - kt_alpha + solver_matmul(k_bb, alpha_b, precision)


def _ridge_diagonal(col_live, lam):
    """λ on the block's real columns, 1 on a ragged block's pad: the padded
    K_BB + diag is block diagonal, so the pad's α stays exactly 0 and the
    system is positive definite at λ = 0 too."""
    return jnp.where(col_live, lam, 1.0)


#: What the store leaves of the device's memory to everything the process
#: holds outside the solver's program, as a divisor of the reported limit:
#: the caller's images, the features before they were scaled, the model of
#: the fit before (2.25 GB of 16.9 in ``cifar-kernel-fit``).
_OUTSIDE_SHARE = 8
#: The program's temporaries beside the store, in blocks: each of its two
#: loops holds the block it works on, and the factorisation's scratch is
#: part of a third (the v5e compile at ``cifar-kernel-fit``'s shape: the
#: store and 2.24 blocks, ``tests/test_aot_tpu.py``).
_WORKING_BLOCKS = 3


def _blocks_kept(rows: int, block: int, num_blocks: int, num_epochs: int,
                 argument_bytes: int, itemsize: int, limit: int) -> int:
    """How many of the first epoch's kernel blocks the solve keeps in HBM
    for the later epochs to read: store or regenerate, from sizes alone, so
    every process of a mesh comes out the same. ``rows`` are one shard's
    padded rows, ``argument_bytes`` its X and Y, ``limit`` the device's
    reported memory. One epoch visits nothing twice and keeps nothing.
    Otherwise as many slots of rows x block as fit beside the arguments,
    the working blocks and ``limit / _OUTSIDE_SHARE`` for what is not this
    program's."""
    if num_epochs < 2:
        return 0
    slot = rows * block * itemsize
    room = limit - limit // _OUTSIDE_SHARE - argument_bytes - _WORKING_BLOCKS * slot
    return max(0, min(num_blocks, room // slot))


@lru_cache(maxsize=None)
def _block_solve_fn(mesh: Mesh, axis: str, precision, fold: int, block: int,
                    keep: int = 0, num_blocks: int = 0):
    """The whole solve as ONE program: a scan over the visits, per shard
    under shard_map. ``starts`` (the first row of each visit's block) is
    an argument, so epochs and order are data; so are λ, the row count and
    the kernel's parameters.

    With ``keep`` > 0 the first ``num_blocks`` visits (one epoch) are a scan
    of their own that carries a store of ``keep`` slots and writes each
    block it generates to slot ``start // block``, where that is under
    ``keep``; the later visits read their block from its slot, and generate
    it as the first epoch did where it has none. The stored block is the
    generated block, bit for bit. With ``keep`` 0 there is one scan and no
    store."""
    width = mesh.shape[axis]

    def local(xl, yl, lam, n, starts, kernel):
        rows = xl.shape[0]
        first = lax.axis_index(axis) * rows
        row_id = first + jnp.arange(rows)
        row_live = row_id < n  # X's pad rows

        def take_block(v, start):
            """Rows [start, start + block) of the row-sharded ``v``,
            replicated: each shard puts in the rows it holds, zeros where
            the block runs past the last row."""
            at = start + jnp.arange(block) - first
            mine = (at >= 0) & (at < rows)
            held = v.at[jnp.clip(at, 0, rows - 1)].get(
                mode="promise_in_bounds", indices_are_sorted=True)
            return lax.psum(jnp.where(mine[:, None], held, 0.0), axis)

        def generate(start):
            with device_scope("krr.generate"):
                col_live = start + jnp.arange(block) < n
                x_b = take_block(xl, start)
                return jnp.where(row_live[:, None] & col_live[None, :],
                                 kernel.block(xl, x_b), 0.0)

        def visit(alpha, start, k_b):
            """One block's solve on its kernel block, made or read."""
            with device_scope("krr.reduce"):
                k_bb = take_block(k_b, start)
                kt_alpha = sharded_rowsum(
                    lambda kb, al: solver_matmul(kb.T, al, precision),
                    axis, width, (k_b, alpha), scope="coll.atr",
                )
                r = _block_residual(take_block(yl, start), kt_alpha, k_bb,
                                    take_block(alpha, start), precision)
            with device_scope("krr.factor"):
                ridge = _ridge_diagonal(start + jnp.arange(block) < n, lam)
                chol = jnp.linalg.cholesky(
                    k_bb + ridge[:, None] * jnp.eye(block, dtype=k_bb.dtype))
            with device_scope("krr.solve"):
                alpha_b = cho_solve((chol, True), r)
                at = row_id - start
                inside = (at >= 0) & (at < block)
                return jnp.where(inside[:, None],
                                 alpha_b[jnp.clip(at, 0, block - 1)], alpha)

        def make(alpha, start):
            return visit(alpha, start, generate(start)), None

        alpha = jnp.zeros_like(yl)
        if not keep:
            alpha, _ = lax.scan(make, alpha, starts)
            return lax.all_gather(alpha, axis, tiled=True)

        def make_and_keep(carry, start):
            alpha, store = carry
            kept = k_b = generate(start)
            with device_scope("krr.generate"):
                slot = start // block
                if keep < num_blocks:
                    # A block with no slot writes back what its neighbour's
                    # slot holds: the store stays out of any conditional.
                    has_slot, slot = slot < keep, jnp.minimum(slot, keep - 1)
                    kept = jnp.where(has_slot, k_b,
                                     lax.dynamic_index_in_dim(store, slot, keepdims=False))
                store = lax.dynamic_update_index_in_dim(store, kept, slot, 0)
            return (visit(alpha, start, k_b), store), None

        (alpha, store), _ = lax.scan(
            make_and_keep,
            (alpha, jnp.zeros((keep, rows, block), xl.dtype)), starts[:num_blocks])

        def fetch(start):
            with device_scope("krr.fetch"):
                return lax.dynamic_index_in_dim(store, start // block, keepdims=False)

        def read_or_make(alpha, start):
            if keep < num_blocks:
                k_b = lax.cond(start // block < keep, fetch, generate, start)
            else:
                k_b = fetch(start)
            return visit(alpha, start, k_b), None

        alpha, _ = lax.scan(read_or_make, alpha, starts[num_blocks:])
        # The model's weights are replicated, as a linear map's are.
        return lax.all_gather(alpha, axis, tiled=True)

    sm = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sm)


def kernel_block_gauss_seidel(
    A: RowMatrix, B: RowMatrix, kernel: KernelGenerator, lam: float,
    block_size: int, num_epochs: int,
) -> jax.Array:
    """α of (K + λI) α = Y after ``num_epochs`` sweeps of block
    Gauss-Seidel from α = 0, for the row-sharded features ``A`` and targets
    ``B``: (padded rows, k), replicated, pad rows 0."""
    A._check_aligned(B)
    mesh, axis = A.mesh, config.data_axis
    block = min(int(block_size), A.n)
    num_blocks = -(-A.n // block)
    visits = _visit_order(num_blocks, num_epochs)
    dtype = A.data.dtype
    rows = A.padded_rows // mesh.shape[axis]
    keep = _blocks_kept(
        rows, block, num_blocks, num_epochs,
        rows * (A.shape[1] + B.shape[1]) * dtype.itemsize, dtype.itemsize,
        device_hbm_bytes())
    if not np.isin(visits[num_blocks:], visits[:num_blocks]).all():
        keep = 0  # not _visit_order's order: a later visit would read an empty slot
    generated = len(visits) - int((visits[num_blocks:] < keep).sum())  # the rest are read
    slot_bytes = A.padded_rows * block * dtype.itemsize
    with span_of(active_tracer(), "krr.fit", "solver", rows=A.n, dim=A.shape[1],
                 block=block, blocks=num_blocks, epochs=num_epochs,
                 kernel_blocks=generated, kernel_bytes=generated * slot_bytes,
                 blocks_kept=keep, store_bytes=keep * slot_bytes):
        solve = _block_solve_fn(
            mesh, axis, _precision(), fold_blocks(mesh.shape[axis]), block,
            keep, num_blocks if keep else 0)
        return solve(A.data, B.data, jnp.asarray(lam, dtype),
                     jnp.asarray(A.n, jnp.int32), jnp.asarray(visits * block), kernel)


class KernelRidgeRegression(LabelEstimator):
    """Kernel ridge regression as upstream solves it: block Gauss-Seidel
    over blocks of ``block_size`` training rows, ``num_epochs`` sweeps, each
    kernel block generated from the features when it is first visited and,
    where the device has the room, kept for the sweeps after."""

    def __init__(self, kernel: KernelGenerator, lam: float = 1.0,
                 block_size: int = 4096, num_epochs: int = 1):
        self.kernel = kernel
        self.lam = lam
        self.block_size = int(block_size)
        self.num_epochs = int(num_epochs)

    def fit(self, data, labels) -> KernelBlockLinearMapper:
        X = jnp.asarray(data, dtype=config.default_dtype)
        Y = jnp.asarray(labels, dtype=config.default_dtype)
        if Y.ndim == 1:
            Y = Y[:, None]
        A = RowMatrix.from_array(X)
        alpha = kernel_block_gauss_seidel(
            A, RowMatrix.from_array(Y), self.kernel, self.lam,
            self.block_size, self.num_epochs)
        return KernelBlockLinearMapper(self.kernel, X, alpha[: A.n], self.block_size)
