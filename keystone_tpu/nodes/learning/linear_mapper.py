"""Dense linear model fit by distributed least squares.

Ref: src/main/scala/nodes/learning/LinearMapper.scala —
`LinearMapEstimator(lambda)` solves ridge least squares on (features,
±1-indicator labels) through ml-matrix, producing `LinearMapper(x, bOpt,
featureScaler)`: scores = (X − μ) W + b [unverified].

TPU lowering: features/labels go row-sharded over the mesh (`RowMatrix`),
the solve is normal equations with `psum`-reduced grams (or TSQR for the
ill-conditioned case), and the fitted mapper is one MXU gemm.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from keystone_tpu.linalg import (
    RowMatrix,
    solve_least_squares_normal,
    solve_least_squares_tsqr,
)
from keystone_tpu.workflow import LabelEstimator, Transformer


class LinearMapper(Transformer):
    array_fields = ("W", "b")

    def __init__(self, W, b: Optional[jax.Array] = None):
        self.W = jnp.asarray(W)
        self.b = None if b is None else jnp.asarray(b)

    def apply_batch(self, X):
        out = X @ self.W
        if self.b is not None:
            out = out + self.b
        return out


class LinearMapEstimator(LabelEstimator):
    """Ridge least squares with an intercept fit by centering.

    The intercept comes from centering both sides (the reference pairs the
    solve with a feature-mean scaler): W solves the centered ridge problem,
    b = ȳ − x̄ᵀW.
    """

    def __init__(self, lam: float = 0.0, method: str = "normal"):
        if method not in ("normal", "tsqr"):
            raise ValueError("method must be 'normal' or 'tsqr'")
        self.lam = lam
        self.method = method

    def partial_fit(self, data, labels, state=None, decay=None,
                    window=None, chunk_rows=None):
        """Fold one labeled batch into retained normal-equation
        accumulators (``workflow.online.OnlineState``) — create the
        state on first call, mutate-and-return it after. The fold is
        grouping-invariant: K calls are bit-identical to one call over
        the concatenation. ``solve_online`` re-solves cheaply."""
        from keystone_tpu.workflow.online import partial_fit_step

        return partial_fit_step(state, data, labels, decay=decay,
                                window=window, chunk_rows=chunk_rows)

    def solve_online(self, state) -> LinearMapper:
        """Re-solve the retained accumulators through the existing
        Cholesky path: the intercept rides the retained weighted means
        (exact rank-one centering correction), matching the batch fit's
        b = ȳ − x̄ᵀW semantics."""
        W, b = state.solve(self.lam)
        return LinearMapper(W, b)

    def fit(self, data, labels) -> LinearMapper:
        from keystone_tpu.linalg.row_matrix import storage_dtype

        X = jnp.asarray(data)
        Y = jnp.asarray(labels)
        # Placement-invariant centering: the means ride the same re-shard
        # + per-shard-sum + psum path as the grams (RowMatrix.col_sums),
        # so the fit is bit-identical whether the features arrived
        # sharded, replicated, or on one device — the data-parallel walk
        # can never perturb a solve. Centering derives on-device from the
        # ONE placed copy (RowMatrix.centered: subtract, re-zero pad
        # rows, cast) — no second host-to-device transfer of X.
        Ax = RowMatrix.from_array(X, dtype=X.dtype)
        Ay = RowMatrix.from_array(Y, dtype=Y.dtype)
        x_mean = Ax.col_sums() / Ax.n
        y_mean = Ay.col_sums() / Ay.n
        from keystone_tpu.config import config

        full = jnp.dtype(config.default_dtype)
        if self.method == "tsqr":
            # QR is storage-dtype-sensitive; TSQR keeps full width.
            W = solve_least_squares_tsqr(
                Ax.centered(x_mean, dtype=full),
                Ay.centered(y_mean, dtype=full),
                self.lam,
            )
        else:
            # Normal equations: A may store bf16 (gram accumulates f32).
            W = solve_least_squares_normal(
                Ax.centered(x_mean, dtype=storage_dtype()),
                Ay.centered(y_mean, dtype=full),
                self.lam,
            )
        b = y_mean - x_mean @ W
        return LinearMapper(W, b)
