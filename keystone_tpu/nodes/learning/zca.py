"""ZCA whitening (the RandomPatchCifar preprocessing).

Ref: src/main/scala/nodes/images/ZCAWhitener.scala —
`ZCAWhitenerEstimator(eps)` fits on the patch matrix via SVD; the whitener
maps x → (x − μ) V (S²/n + εI)^(−1/2) Vᵀ (SURVEY.md §2.4) [unverified].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from keystone_tpu.workflow import Estimator, Transformer


class ZCAWhitener(Transformer):
    array_fields = ("whitener", "mean")

    def __init__(self, whitener: jax.Array, mean: jax.Array):
        self.whitener = jnp.asarray(whitener)  # (d, d)
        self.mean = jnp.asarray(mean)

    def apply_batch(self, X):
        return jnp.matmul(
            X - self.mean, self.whitener, precision=lax.Precision.HIGHEST
        )


@jax.jit
def _fit_zca(X, eps):
    """(whitener, mean) of the rows of ``X``: one program, on the device
    the rows are on."""
    mean = X.mean(axis=0)
    Xc = X - mean
    # Eigendecomposition of the covariance (symmetric, stable on TPU).
    cov = jnp.matmul(Xc.T, Xc, precision=lax.Precision.HIGHEST) / X.shape[0]
    evals, evecs = jnp.linalg.eigh(cov)
    scale = 1.0 / jnp.sqrt(jnp.maximum(evals, 0.0) + eps)
    whitener = jnp.matmul(
        evecs * scale, evecs.T, precision=lax.Precision.HIGHEST
    )
    return whitener, mean


class ZCAWhitenerEstimator(Estimator):
    def __init__(self, eps: float = 0.1):
        self.eps = eps

    def fit(self, data) -> ZCAWhitener:
        return ZCAWhitener(*_fit_zca(jnp.asarray(data), float(self.eps)))
