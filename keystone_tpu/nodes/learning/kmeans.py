"""K-Means++ clustering.

Ref: src/main/scala/nodes/learning/KMeansPlusPlus.scala —
`KMeansPlusPlusEstimator(k, maxIters)` with kmeans++ seeding; `KMeansModel`
transforms a vector to the one-hot encoding of its nearest center (the
feature-encoding use in pipelines) [unverified].

TPU lowering: Lloyd iterations are one fused computation per sweep —
pairwise distances (MXU gemm), argmin, segment-sum recentering — scanned
with lax.fori_loop so the whole fit is a single XLA program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.config import config
from keystone_tpu.utils.metrics import active_tracer, span_of, upload_nbytes
from keystone_tpu.workflow import Estimator, Transformer


from keystone_tpu.nodes.learning.kernels import pairwise_sq_dists as _sq_dists


class KMeansModel(Transformer):
    def __init__(self, centers: jax.Array):
        self.centers = jnp.asarray(centers)

    def apply_batch(self, X):
        """One-hot nearest-center encoding (the reference's transform)."""
        assign = jnp.argmin(_sq_dists(X, self.centers), axis=1)
        return jax.nn.one_hot(
            assign, self.centers.shape[0], dtype=config.default_dtype
        )

    def predict(self, X):
        return jnp.argmin(_sq_dists(jnp.asarray(X), self.centers), axis=1)


@partial(jax.jit, static_argnames=("k", "max_iters"))
def _fit_kmeans(X, key, k: int, max_iters: int):
    n = X.shape[0]

    # -- kmeans++ seeding (distance-weighted sampling) --
    def seed_step(i, carry):
        centers, d2, key = carry
        key, sub = jax.random.split(key)
        probs = d2 / jnp.maximum(d2.sum(), 1e-12)
        idx = jax.random.choice(sub, n, p=probs)
        centers = centers.at[i].set(X[idx])
        new_d2 = jnp.sum((X - X[idx]) ** 2, axis=1)
        return centers, jnp.minimum(d2, new_d2), key

    key, sub = jax.random.split(key)
    first = X[jax.random.randint(sub, (), 0, n)]
    centers0 = jnp.zeros((k, X.shape[1]), X.dtype).at[0].set(first)
    d2_0 = jnp.sum((X - first) ** 2, axis=1)
    centers, _, key = jax.lax.fori_loop(
        1, k, seed_step, (centers0, d2_0, key)
    )

    # -- Lloyd iterations --
    def lloyd(_i, centers):
        assign = jnp.argmin(_sq_dists(X, centers), axis=1)
        onehot = jax.nn.one_hot(assign, k, dtype=X.dtype)  # (n, k)
        counts = onehot.sum(axis=0)  # (k,)
        sums = onehot.T @ X  # (k, d) — MXU
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # Keep old center for empty clusters.
        return jnp.where((counts > 0)[:, None], new, centers)

    return jax.lax.fori_loop(0, max_iters, lloyd, centers)


class KMeansPlusPlusEstimator(Estimator):
    def __init__(self, k: int, max_iters: int = 20, seed: int = 0):
        self.k = k
        self.max_iters = max_iters
        self.seed = seed

    def fit(self, data) -> KMeansModel:
        with span_of(active_tracer(), "kmeans.fit", "featurizer",
                     bytes=upload_nbytes(data)):
            X = jnp.asarray(data, dtype=config.default_dtype)
            centers = _fit_kmeans(
                X, jax.random.PRNGKey(self.seed), self.k, self.max_iters
            )
            return KMeansModel(centers)
