"""Block least squares — the workhorse solver for high-dimensional features.

Ref: src/main/scala/nodes/learning/BlockLinearMapper.scala —
`BlockLeastSquaresEstimator(blockSize, numIter, lambda)` runs ml-matrix
BlockCoordinateDescent over feature blocks and returns `BlockLinearMapper`
(per-block weights applied block-by-block); the CIFAR/TIMIT workhorse.
`BlockWeightedLeastSquaresEstimator(..., mixtureWeight)` is the
class-rebalanced ImageNet variant (SURVEY.md §2.4, §3.2) [unverified].

TPU lowering: see keystone_tpu/linalg/bcd.py. The intercept is fit by
centering features and labels (b = ȳ − x̄ᵀW), matching the reference's
mean-scaler pairing.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.config import config
from keystone_tpu.linalg import (
    RowMatrix,
    block_coordinate_descent,
    block_coordinate_descent_streamed,
)
from keystone_tpu.workflow import LabelEstimator, Transformer


class BlockLinearMapper(Transformer):
    """Applies W block-by-block: scores = Σ_b X_b W_b + b.

    Keeping per-block weights (instead of one dense (d, k) matrix) is what
    lets a 256k-dim model stream through memory; XLA fuses the per-block
    gemm+accumulate chain.
    """

    # The weights are arguments of the program; the column ranges are its
    # static part (a tuple of tuples, so that it hashes).
    array_fields = ("W_blocks", "b")

    def __init__(
        self,
        W_blocks: Sequence[jax.Array],
        blocks: Sequence[Tuple[int, int]],
        b: Optional[jax.Array] = None,
    ):
        self.W_blocks = [jnp.asarray(w) for w in W_blocks]
        self.blocks = tuple((int(s), int(e)) for s, e in blocks)
        self.b = None if b is None else jnp.asarray(b)

    def apply_batch(self, X):
        from keystone_tpu.utils.sparse import SparseBatch

        out = None
        if isinstance(X, SparseBatch):
            # matmul densifies per column block internally — same streaming
            # shape as the dense loop below, one implementation.
            out = X.matmul(np.asarray(self.W))
            if self.b is not None:
                out = out + np.asarray(self.b)
            return out
        for (s, e), w in zip(self.blocks, self.W_blocks):
            # HIGHEST like the solve that produced W: at the TPU default
            # (one bf16 pass) a 65,536-term score is 3e-3 off float32
            # (measured on a v5e).
            contrib = jnp.matmul(
                X[..., s:e], w, precision=jax.lax.Precision.HIGHEST
            )
            out = contrib if out is None else out + contrib
        if self.b is not None:
            out = out + self.b
        return out

    @property
    def W(self) -> jax.Array:
        return jnp.concatenate(self.W_blocks, axis=0)


def resolve_block_size(block_size, d: int) -> int:
    """Resolve ``block_size="auto"`` to the largest memory-safe block.

    Larger blocks mean bigger MXU gemms and fewer sequentially-lowered
    factorizations (the 2026-07-29 one-chip sweep rose ~8× in solver
    TFLOPS from block 1024 to 8192), so auto picks the smallest power of
    two that covers d — i.e. a single exact block whenever d fits — capped
    at 8192 on accelerators (4096, the historical fixed default, on CPU,
    whose factorizations don't tile) and shrunk until the cached ridge
    inverses (d·b bytes) stay within a quarter of what the device reports
    (``device_hbm_bytes``), the same envelope the gram-cache auto rule
    assumes."""
    if block_size != "auto":
        return int(block_size)
    from keystone_tpu.utils.metrics import device_hbm_bytes

    cap = 4096 if jax.default_backend() == "cpu" else 8192
    b = min(cap, 1 << int(np.ceil(np.log2(max(d, 128)))))
    itemsize = jnp.dtype(config.default_dtype).itemsize
    while b > 128 and d * b * itemsize > device_hbm_bytes() // 4:
        b //= 2
    return b


class BlockLeastSquaresEstimator(LabelEstimator):
    def __init__(
        self,
        block_size="auto",
        num_iters: int = 1,
        lam: float = 0.0,
        fit_intercept: bool = True,
        checkpoint_dir: Optional[str] = None,
        stream: Optional[bool] = None,
        parallelism: str = "data",
    ):
        if parallelism not in ("data", "model"):
            raise ValueError("parallelism must be 'data' or 'model'")
        self.block_size = block_size
        self.num_iters = num_iters
        self.lam = lam
        self.fit_intercept = fit_intercept
        # Epoch-boundary solver checkpointing (orbax); resumes on refit.
        self.checkpoint_dir = checkpoint_dir
        # Host-streamed feature blocks (double-buffered H2D) for feature
        # matrices that exceed HBM; None = auto by size.
        self.stream = stream
        # "data": rows sharded, psum'd grams (the default). "model": the
        # d-axis shards across the mesh and residual chunks ride a ppermute
        # ring (linalg/ring_bcd.py). Measured guidance (tools/bench_ring.py
        # on the 8-device mesh, n=256 k=4 iters=2): ring 5.5x faster at
        # d=n·k and 17.7x at d=8·n·k — the ring shards the per-block
        # factorizations across chips while the data path REPLICATES each
        # post-psum b x b inverse on every chip, and it moves n·k/P-sized
        # residual chunks instead of psum'ing b x b grams. Prefer "model"
        # whenever d well exceeds n·k and features are dense; prefer
        # "data" for tall-skinny problems (n >> d), sparse features, or
        # when per-chip HBM can't hold an (n, d/P) column shard.
        self.parallelism = parallelism

    def _weights(self, Y: jnp.ndarray) -> Optional[jax.Array]:
        return None

    def partial_fit(self, data, labels, state=None, decay=None,
                    window=None, chunk_rows=None):
        """Fold one labeled batch into retained normal-equation
        accumulators (``workflow.online.OnlineState``). The online
        re-solve is the EXACT dense normal-equation solution (not the
        BCD approximation), so it requires the (d, d) gram to be
        materializable — the usual online regime (features already
        reduced by the frozen featurize prefix)."""
        from keystone_tpu.workflow.online import partial_fit_step

        return partial_fit_step(state, data, labels, decay=decay,
                                window=window, chunk_rows=chunk_rows)

    def solve_online(self, state) -> BlockLinearMapper:
        """Re-solve the retained accumulators as one dense block (the
        exact solution of the streamed problem), wrapped in the same
        ``BlockLinearMapper`` interface the batch fit produces."""
        W, b = state.solve(self.lam, fit_intercept=self.fit_intercept)
        return BlockLinearMapper([W], [(0, state.d)], b)

    def fit(self, data, labels) -> BlockLinearMapper:
        from keystone_tpu.utils.sparse import SparseBatch

        if isinstance(data, SparseBatch):
            if self.parallelism == "model":
                raise ValueError(
                    "model parallelism is a dense-feature path; sparse "
                    "features use the streamed data-parallel solve"
                )
            return self._fit_sparse(data, labels)
        if self.parallelism == "model":
            return self._fit_ring(data, labels)
        block_size = resolve_block_size(
            self.block_size, int(np.shape(data)[-1])
        )
        stream = self.stream
        itemsize = jnp.dtype(config.default_dtype).itemsize
        if stream is None:
            from keystone_tpu.utils.mesh import num_data_shards
            from keystone_tpu.utils.metrics import device_hbm_bytes

            # What ONE device would hold: the solve shards the rows over
            # the mesh wherever they arrived, so a matrix four chips hold a
            # quarter each of is not one that "exceeds HBM" (counted whole,
            # 32,768 x 65,536 rows on a v5e-4 went to the host and back a
            # block at a time: 45 s a fit for 2: PERF.md, PR 38).
            a_bytes = int(np.prod(np.shape(data))) * itemsize
            stream = a_bytes // num_data_shards() > device_hbm_bytes() // 2

        if stream:
            # Features stay in host RAM — the caller's array, uncopied and
            # unmodified: centering happens per block as it streams
            # (col_center), so peak memory is A + one block, never 2·A.
            X_host = np.asarray(data, dtype=config.default_dtype)
            Y = jnp.asarray(labels)
            weights = self._weights(Y)
            # Labels are placed ONCE (they ride the solve as B anyway);
            # centering derives on-device from that copy
            # (RowMatrix.centered) — no second label transfer.
            Ay = RowMatrix.from_array(Y)
            x_mean = y_mean = None
            if self.fit_intercept:
                # Same math and guard as the device path below (weighted
                # means with a wsum floor), computed host-side for X
                # (host-resident by contract); label means ride the
                # psum'd re-shard path so the streamed fit is invariant
                # to the LABELS' arrival placement.
                if weights is None:
                    x_mean = X_host.mean(axis=0, dtype=X_host.dtype)
                    y_mean = Ay.col_sums() / Ay.n
                else:
                    w_np = np.asarray(weights, dtype=X_host.dtype)
                    wsum = max(float(w_np.sum()), 1e-12)
                    # matvec, not (w[:,None] * X).sum(0): no X-sized temporary
                    # on the path that exists because X barely fits in RAM.
                    x_mean = (w_np @ X_host) / wsum
                    Aw = RowMatrix.from_array(weights[:, None])
                    y_mean = Ay.weighted_col_sums(Aw) / jnp.maximum(
                        Aw.col_sums()[0], 1e-12
                    )
            B = Ay if y_mean is None else Ay.centered(y_mean)
            W_blocks, blocks = block_coordinate_descent_streamed(
                X_host,
                B,
                block_size=block_size,
                num_iters=self.num_iters,
                lam=self.lam,
                row_weights=weights,
                checkpoint_dir=self.checkpoint_dir,
                col_center=None if x_mean is None else np.asarray(x_mean),
            )
            b = None
            if self.fit_intercept:
                W = jnp.concatenate(W_blocks, axis=0)
                b = jnp.asarray(y_mean) - jnp.asarray(
                    x_mean, dtype=W.dtype
                ) @ W
            return BlockLinearMapper(W_blocks, blocks, b)

        from keystone_tpu.linalg.row_matrix import storage_dtype
        from keystone_tpu.utils.metrics import active_tracer, span_of

        # What the device path dispatches in front of the solve: weights,
        # means, centring, the padded row matrices. It waits for nothing.
        with span_of(active_tracer(), "solver.setup", "solver",
                     rows=int(np.shape(data)[0]), dim=int(np.shape(data)[-1]),
                     classes=int(np.shape(labels)[-1]) if np.ndim(labels) > 1 else 1):
            X = jnp.asarray(data)
            Y = jnp.asarray(labels)
            weights = self._weights(Y)
            full = jnp.dtype(config.default_dtype)
            x_mean = y_mean = None
            if self.fit_intercept:
                # Weighted problems need weighted centering: the intercept
                # of weighted ridge absorbs the weighted means, b = ȳ_w −
                # x̄_wᵀW. The means ride the same re-shard + per-shard-sum +
                # psum path as the grams (RowMatrix.col_sums), so a fit over
                # a sharded batch is bit-identical to one over the same
                # bytes on a single device — no host-side fold, and no
                # dependence on whatever placement the features arrived
                # with. Centering derives on-device from the ONE placed copy
                # (RowMatrix.centered: subtract, re-zero pad rows, cast) —
                # no second host-to-device transfer of X.
                Ax = RowMatrix.from_array(X, dtype=X.dtype)
                Ay = RowMatrix.from_array(Y, dtype=Y.dtype)
                if weights is None:
                    x_mean = Ax.col_sums() / Ax.n
                    y_mean = Ay.col_sums() / Ay.n
                else:
                    Aw = RowMatrix.from_array(
                        weights[:, None], dtype=weights.dtype
                    )
                    wsum = jnp.maximum(Aw.col_sums()[0], 1e-12)
                    x_mean = Ax.weighted_col_sums(Aw) / wsum
                    y_mean = Ay.weighted_col_sums(Aw) / wsum
                A = Ax.centered(x_mean, dtype=storage_dtype())
                B = Ay.centered(y_mean, dtype=full)
            else:
                A = RowMatrix.from_array(X, dtype=storage_dtype())
                B = RowMatrix.from_array(Y)
        W_blocks, blocks = block_coordinate_descent(
            A,
            B,
            block_size=block_size,
            num_iters=self.num_iters,
            lam=self.lam,
            row_weights=weights,
            checkpoint_dir=self.checkpoint_dir,
        )
        b = None
        if self.fit_intercept:
            W = jnp.concatenate(W_blocks, axis=0)
            b = y_mean - x_mean @ W
        return BlockLinearMapper(W_blocks, blocks, b)


    def _fit_ring(self, data, labels) -> BlockLinearMapper:
        """Model-parallel fit: columns of A shard across the mesh and the
        residual chunks ride a ppermute ring (no gram psum, no all-gather —
        see linalg/ring_bcd.py for the layout and comm accounting)."""
        from keystone_tpu.linalg import block_coordinate_descent_ring

        if self._weights(jnp.asarray(labels)) is not None:
            raise ValueError(
                "the ring solver has no per-row weighting; use "
                "parallelism='data' for the class-weighted problem"
            )
        if self.checkpoint_dir is not None or self.stream:
            # Refuse rather than silently drop resume/streaming semantics
            # the data-parallel path would have honored.
            raise ValueError(
                "checkpoint_dir/stream are data-parallel features; the ring "
                "solver keeps its d-shard resident and has no epoch "
                "checkpointing (block_size is likewise implicit: each chip's "
                "block is d / ring size)"
            )
        X = np.asarray(data, dtype=config.default_dtype)
        Y = np.asarray(labels, dtype=config.default_dtype)
        x_mean = y_mean = None
        if self.fit_intercept:
            x_mean = X.mean(axis=0)
            y_mean = Y.mean(axis=0)
            # Center in place on an owned copy: X - mean would hold a second
            # full (n, d) array on the path meant for the largest d. When the
            # input was a jax.Array, np.asarray gives a read-only zero-copy
            # view (X is not data yet not writeable) — copy in that case too.
            if X is data or not X.flags.writeable:
                X = np.array(X, copy=True)
            np.subtract(X, x_mean, out=X)
            Y = Y - y_mean
        W = block_coordinate_descent_ring(
            X, Y, num_iters=self.num_iters, lam=self.lam
        )
        b = None
        if self.fit_intercept:
            b = jnp.asarray(y_mean) - jnp.asarray(x_mean) @ W
        return BlockLinearMapper([W], [(0, X.shape[1])], b)

    def _fit_sparse(self, data, labels) -> BlockLinearMapper:
        """Large-vocab path: CSR features stream to the device one dense
        column block at a time (an (n, vocab) dense array never exists).

        The intercept is learned as the weight of an appended all-ones
        column (centering would destroy sparsity); with lam > 0 the
        intercept is therefore ridge-penalized too — a small documented
        deviation from the centered dense path, exact at lam = 0.
        """
        Y = jnp.asarray(labels)
        weights = self._weights(Y)
        A = data.append_ones() if self.fit_intercept else data
        B = RowMatrix.from_array(Y)
        W_blocks, blocks = block_coordinate_descent_streamed(
            A,
            B,
            block_size=resolve_block_size(self.block_size, data.shape[1]),
            num_iters=self.num_iters,
            lam=self.lam,
            row_weights=weights,
            checkpoint_dir=self.checkpoint_dir,
        )
        b = None
        if self.fit_intercept:
            last = W_blocks[-1]
            b = last[-1]
            if last.shape[0] == 1:  # the ones column was its own block
                W_blocks = W_blocks[:-1]
                blocks = blocks[:-1]
            else:
                s, e = blocks[-1]
                W_blocks = W_blocks[:-1] + [last[:-1]]
                blocks = blocks[:-1] + [(s, e - 1)]
        return BlockLinearMapper(W_blocks, blocks, b)


class BlockWeightedLeastSquaresEstimator(BlockLeastSquaresEstimator):
    """Class-rebalanced block least squares.

    Each example of class c gets weight
        w = (1 − mixture_weight) + mixture_weight · n / (k · n_c),
    i.e. mixture_weight interpolates between the unweighted problem (0) and
    fully class-balanced weighting (1). Reconstruction of the reference's
    `mixtureWeight` semantics [unverified — verify against
    nodes/learning/BlockWeightedLeastSquaresEstimator.scala].
    """

    def __init__(
        self,
        block_size="auto",
        num_iters: int = 1,
        lam: float = 0.0,
        mixture_weight: float = 0.5,
        fit_intercept: bool = True,
        checkpoint_dir: Optional[str] = None,
        stream: Optional[bool] = None,
        parallelism: str = "data",
    ):
        super().__init__(
            block_size,
            num_iters,
            lam,
            fit_intercept,
            checkpoint_dir,
            stream,
            parallelism,
        )
        self.mixture_weight = mixture_weight

    # Class-rebalanced weights need the class counts of the FULL label
    # set — a per-batch fold cannot know them, so the online contract is
    # nulled out (supports_partial_fit -> False; Pipeline.refit_stream
    # falls back to the counted full refit and KG105 warns statically).
    partial_fit = None
    solve_online = None

    def _weights(self, Y: jnp.ndarray) -> Optional[jax.Array]:
        if self.mixture_weight == 0.0:
            return None  # exactly the unweighted problem
        # Y may be centered; class identity is still the row-wise argmax of
        # the ±1 indicator encoding.
        classes = jnp.argmax(Y, axis=1)
        k = Y.shape[1]
        n = Y.shape[0]
        # The classes' counts are column sums of the rows' indicators: over
        # sharded rows they reduce as the means do (``RowMatrix.col_sums``),
        # where a ``bincount`` would be left to the partitioner.
        counts = RowMatrix.from_array(
            jax.nn.one_hot(classes, k, dtype=Y.dtype), dtype=Y.dtype
        ).col_sums()
        counts = jnp.maximum(counts, 1.0)
        per_class = (1.0 - self.mixture_weight) + self.mixture_weight * n / (
            k * counts
        )
        return per_class[classes]
