"""PCA: local SVD and distributed (TSQR-based) variants.

Ref: src/main/scala/nodes/learning/PCA.scala — `PCAEstimator` (driver-local
SVD via Breeze/LAPACK gesdd) and `DistributedPCAEstimator` (TSQR-based),
both producing `PCATransformer` projecting onto the top components
(SURVEY.md §2.4, §3.4: PCA of SIFT descriptors) [unverified].

TPU lowering: the local variant is one on-device SVD of the centered data;
the distributed variant reduces the row-sharded data to its (d, d) R factor
by TSQR (all_gather over ICI), then SVDs the small R — identical right
singular vectors, no n×d gather.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from keystone_tpu.linalg import RowMatrix, tsqr_r
from keystone_tpu.utils.metrics import active_tracer, span_of, upload_nbytes
from keystone_tpu.workflow import Estimator, Transformer


class PCATransformer(Transformer):
    # The fitted arrays, arguments of the program (``mean`` may be None:
    # then it is no argument, and another program).
    array_fields = ("components", "mean")

    def __init__(self, components: jax.Array, mean: jax.Array | None = None):
        # components: (d, dims) — columns are principal directions.
        self.components = jnp.asarray(components)
        self.mean = None if mean is None else jnp.asarray(mean)

    def signature(self):
        # Content-stable from the fitted parameters: prefixes THROUGH a
        # fitted PCA stay persistable, so downstream fits (the flagship
        # solver) can hit the cross-process cache. Computed once — this is
        # called on every executor walk and the fingerprint costs a
        # device-to-host fetch.
        sig = getattr(self, "_sig", None)
        if sig is None:
            import numpy as np

            from keystone_tpu.workflow.fingerprint import array_fingerprint

            sig = self.stable_signature(
                array_fingerprint(np.asarray(self.components)),
                None
                if self.mean is None
                else array_fingerprint(np.asarray(self.mean)),
            )
            self._sig = sig
        return sig

    def apply_batch(self, X):
        if self.mean is not None:
            X = X - self.mean
        # HIGHEST: at the TPU default (one bf16 pass) the projection is
        # 3e-3 off float32 (measured on a v5e), and the Fisher-vector
        # log-likelihoods downstream amplify it.
        return jnp.matmul(
            X, self.components, precision=jax.lax.Precision.HIGHEST
        )


def _components_from_r(R: jax.Array, dims: int) -> jax.Array:
    # Right singular vectors of the data = eigenvectors of RᵀR.
    _u, _s, vt = jnp.linalg.svd(R, full_matrices=False)
    return vt[:dims].T


class PCAEstimator(Estimator):
    """Un-sharded SVD PCA (the sample sizes the reference uses fit easily)."""

    def __init__(self, dims: int, center: bool = True):
        self.dims = dims
        self.center = center

    def fit(self, data) -> PCATransformer:
        # Upload of the sample and dispatch of the SVD: the device runs it
        # after the span has closed.
        with span_of(active_tracer(), "pca.fit", "featurizer",
                     bytes=upload_nbytes(data)):
            X = jnp.asarray(data)
            mean = X.mean(axis=0) if self.center else None
            Xc = X - mean if self.center else X
            _u, _s, vt = jnp.linalg.svd(Xc, full_matrices=False)
            return PCATransformer(vt[: self.dims].T, mean)


class DistributedPCAEstimator(Estimator):
    """PCA via TSQR of the row-sharded (centered) data matrix."""

    def __init__(self, dims: int, center: bool = True):
        self.dims = dims
        self.center = center

    def fit(self, data) -> PCATransformer:
        X = jnp.asarray(data)
        mean = X.mean(axis=0) if self.center else None
        Xc = X - mean if self.center else X
        R = tsqr_r(RowMatrix.from_array(Xc))
        return PCATransformer(_components_from_r(R, self.dims), mean)
