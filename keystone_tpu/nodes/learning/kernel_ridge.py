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
(``row_matrix._blocks_kept``, the rule the dense solver's generated blocks
share: one shard's rows, the block, the epochs, the device's reported
memory). One epoch, or slots that do not fit, keep nothing: each
block is used and dropped, no more of K than one block ever live. Otherwise
the first epoch writes the blocks it generates into a store that lives and
dies inside the program, and the later epochs read them: upstream's
``cacheKernel``, without the flag. A slot holds its block as the visit used
it. The writes and the reads are the dense generated solve's too
(``row_matrix.keep_block``, ``read_or_make``); that solve hands its store in
as a donated argument instead, which the runtime frees after the fit, where
a store inside the program stays reserved for as long as the program is
loaded. K is symmetric, so on a mesh one shard wide a block with a store
beside it is made by row tiles of ``block``: its rows B_t where slot t is
written already are that slot's rows of the block, transposed
(K[B_t, B] = K[B, B_t]ᵀ), and only the other tiles are generated, so each
entry of K is made once. Across shards the transpose's rows live on
another chip, and every block is generated whole. X_B is replicated for
the visit (each shard's rows of the block, ``psum``), K_Bᵀ α reduces over
the sharded rows (``sharded_rowsum``), and the b x b solve runs replicated
on every chip. A ragged last block and the pad rows of X are masked out
of K; the pad columns get 1 on the diagonal, so their α stays 0. X, Y, λ,
the row count, the visit order and the kernel's parameters are arguments
of the program: a second fit compiles nothing.

The matrix-free conjugate-gradient solver that bore this name is
``KernelRidgeCG`` (``kernel_ridge_cg.py``).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.experimental.layout import Layout, with_layout_constraint
from jax.scipy.linalg import cho_solve
from jax.sharding import Mesh, PartitionSpec as P

from keystone_tpu.config import config
from keystone_tpu.linalg.row_matrix import (
    RowMatrix,
    _blocks_kept,
    _precision,
    keep_block,
    read_or_make,
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


def _mirrored_tiles(visits: np.ndarray, num_blocks: int, keep: int) -> int:
    """How many row tiles of the blocks it makes a one-shard solve copies
    from its store (``_block_solve_fn``'s ``assemble``): at each block made,
    the leading run of slots the first epoch has written so far. Each is a
    whole tile of live rows: the run never takes in the last block's slot,
    which the first epoch writes after all the others or a later epoch
    that makes blocks again does not have."""
    written, tiles = np.zeros(keep, bool), 0
    for visit, b in enumerate(visits):
        first_epoch = visit < num_blocks
        if first_epoch or b >= keep:  # made, not read
            tiles += int(np.cumprod(written).sum())
        if first_epoch and b < keep:
            written[b] = True
    return tiles


def _block_residual(y_b, kt_alpha, k_bb, alpha_b, precision):
    """R = Y_B − K_Bᵀ α + K_BB α_B: the targets less what the other blocks
    explain."""
    return y_b - kt_alpha + solver_matmul(k_bb, alpha_b, precision)


def _ridge_diagonal(col_live, lam):
    """λ on the block's real columns, 1 on a ragged block's pad: the padded
    K_BB + diag is block diagonal, so the pad's α stays exactly 0 and the
    system is positive definite at λ = 0 too."""
    return jnp.where(col_live, lam, 1.0)


_ROW_MAJOR = Layout(major_to_minor=(0, 1))


@lru_cache(maxsize=None)
def _block_solve_fn(mesh: Mesh, axis: str, precision, fold: int, block: int,
                    keep: int = 0, num_blocks: int = 0):
    """The whole solve as ONE program: a scan over the visits, per shard
    under shard_map. ``starts`` (the first row of each visit's block) is
    an argument, so epochs and order are data; so are λ, the row count and
    the kernel's parameters.

    With ``keep`` > 0 the first ``num_blocks`` visits (one epoch) are a scan
    of their own that carries a store of ``keep`` slots and writes each
    block it makes to slot ``start // block``, where that is under
    ``keep``; the later visits read their block from its slot, and make it
    again where it has none. A slot holds the block as its visit used it.
    With ``keep`` 0 there is one scan and no store.

    On one shard with a store (``mirror``) a block is made by row tiles of
    ``block``: the tiles of the leading run of slots written so far in this
    fit are copied from those slots, transposed (``assemble``), the others
    generated. The first epoch carries which slots it has written; in
    ``_visit_order``'s natural order visit j copies tiles min(j, keep).
    ``_mirrored_tiles`` counts the same on the host. On a wider mesh every
    block is generated whole."""
    width = mesh.shape[axis]
    mirror = bool(keep) and width == 1

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

        def assemble(store, start, m):
            """One shard's block at ``start`` by row tiles of ``block``:
            tiles m and on generated (the last one the rows that are left),
            then tiles 0 to m - 1 copied from their slots: slot t holds
            K[:, B_t], so its rows of this block are K[B_t, B] transposed.
            The block is held row major: left free, XLA lays it out
            transposed to spare the copies their transposes, and then
            transposes the whole block to write it to the store."""
            col_live = start + jnp.arange(block) < n
            last = (rows - 1) // block * block
            with device_scope("krr.generate"):
                x_b = take_block(xl, start)

                def made(at, size):
                    live = (at + jnp.arange(size) < n)[:, None] & col_live[None, :]
                    return jnp.where(
                        live, kernel.block(lax.dynamic_slice_in_dim(xl, at, size), x_b), 0.0)

                k_b = jnp.zeros((rows, block), xl.dtype).at[last:].set(made(last, rows - last))
                k_b = lax.fori_loop(
                    m, last // block, lambda t, k_b: lax.dynamic_update_slice_in_dim(
                        k_b, made(t * block, block), t * block, 0), k_b)
            with device_scope("krr.mirror"):

                def mirrored(t, k_b):
                    at = jnp.clip(start + jnp.arange(block), 0, rows - 1)
                    k_bt = store.at[t, at].get(mode="promise_in_bounds",
                                               indices_are_sorted=True)
                    return with_layout_constraint(lax.dynamic_update_slice_in_dim(
                        k_b, jnp.where(col_live[None, :], k_bt.T, 0.0), t * block, 0),
                        _ROW_MAJOR)

                return lax.fori_loop(0, m, mirrored, k_b)

        def leading(written):
            """How many slots from the first on are written."""
            return jnp.sum(jnp.cumprod(written.astype(jnp.int32)))

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
            alpha, store, *written = carry
            if mirror:
                k_b = assemble(store, start, leading(written[0]))
                written = [written[0] | (jnp.arange(keep) == start // block)]
            else:
                k_b = generate(start)
            with device_scope("krr.generate"):
                store = keep_block(store, k_b, start // block, keep, num_blocks)
            return (visit(alpha, start, k_b), store, *written), None

        (alpha, store, *written), _ = lax.scan(
            make_and_keep,
            (alpha, jnp.zeros((keep, rows, block), xl.dtype),
             *([jnp.zeros(keep, bool)] if mirror else [])),
            starts[:num_blocks])

        def fetch(start):
            with device_scope("krr.fetch"):
                return lax.dynamic_index_in_dim(store, start // block, keepdims=False)

        def make_again(start):
            return assemble(store, start, leading(written[0])) if mirror else generate(start)

        def later(alpha, start):
            k_b = read_or_make(start // block < keep, keep, num_blocks, fetch, make_again, start)
            return visit(alpha, start, k_b), None

        alpha, _ = lax.scan(later, alpha, starts[num_blocks:])
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
    mirrored = _mirrored_tiles(visits, num_blocks, keep) if mesh.shape[axis] == 1 else 0
    slot_bytes = A.padded_rows * block * dtype.itemsize
    with span_of(active_tracer(), "krr.fit", "solver", rows=A.n, dim=A.shape[1],
                 block=block, blocks=num_blocks, epochs=num_epochs,
                 kernel_blocks=generated, kernel_bytes=generated * slot_bytes,
                 blocks_kept=keep, store_bytes=keep * slot_bytes,
                 mirrored_rows=mirrored * block):
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
