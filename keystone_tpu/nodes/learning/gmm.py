"""Diagonal-covariance Gaussian mixture fit by EM.

Ref: src/main/scala/nodes/learning/GaussianMixtureModel.scala —
`GaussianMixtureModelEstimator` (Breeze EM) and the EncEval-backed external
variant used for Fisher vectors; diagonal covariances (SURVEY.md §2.4,
§3.4) [unverified].

TPU lowering: each EM sweep is responsibilities (log-space gemm-shaped
computation + logsumexp) and moment re-estimation (two MXU gemms), scanned
with lax.fori_loop into a single XLA program. This is the pure-TPU GMM; the
C++ EncEval-parity implementation lives in keystone_tpu/native.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from keystone_tpu.config import config
from keystone_tpu.linalg.row_matrix import count_reduced, sharded_rowsum
from keystone_tpu.nodes.learning.kmeans import _fit_kmeans, _sq_dists
from keystone_tpu.utils.mesh import SpecLayout, pad_multiple
from keystone_tpu.utils.metrics import (
    active_tracer,
    device_scope,
    span_of,
    upload_nbytes,
)
from keystone_tpu.workflow import Estimator, Transformer

# HIGHEST precision throughout: ||(x - μ)/σ||² is expanded into gemm-shaped
# terms of order 1e2..1e3 that cancel, and the moments ex2 - mean² cancel
# again. At the TPU default (one bf16 pass) the EM converges to a different
# mixture than float32 does (measured on a v5e; see fisher_vector._fv_tpu).
_mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def _quad(X, means, inv):
    """(n, k) ||(x - μ_j)/σ_j||², gemm-shaped."""
    return (
        _mm(X * X, inv.T)
        - 2.0 * _mm(X, (means * inv).T)
        + jnp.sum(means * means * inv, axis=1)
    )


class GaussianMixtureModel(Transformer):
    """Fitted GMM. As a transformer it emits per-component soft assignments
    (responsibilities) — the quantity Fisher-vector encoding consumes."""

    array_fields = ("weights", "means", "variances")

    def __init__(self, weights, means, variances):
        self.weights = jnp.asarray(weights)  # (k,)
        self.means = jnp.asarray(means)  # (k, d)
        self.variances = jnp.asarray(variances)  # (k, d)

    def log_likelihoods(self, X):
        """(n, k) log p(x | component j) + log w_j."""
        X = jnp.asarray(X)
        quad = _quad(X, self.means, 1.0 / self.variances)
        log_det = jnp.sum(jnp.log(self.variances), axis=1)
        d = X.shape[1]
        log_norm = -0.5 * (d * jnp.log(2 * jnp.pi) + log_det)
        return jnp.log(self.weights) + log_norm - 0.5 * quad

    def apply_batch(self, X):
        ll = self.log_likelihoods(X)
        return jax.nn.softmax(ll, axis=-1)

    def predict(self, X):
        return jnp.argmax(self.log_likelihoods(X), axis=-1)


def _sweep_sums(X, weights, means, variances):
    """What one EM sweep sums over the rows ``X``: their responsibilities
    under the mixture, and the responsibilities' mass, first and second
    moments, each a sum over rows (so the rows may be any share of the
    sample)."""
    d = X.shape[1]
    with device_scope("gmm.estep"):
        quad = _quad(X, means, 1.0 / variances)
        log_norm = -0.5 * (
            d * jnp.log(2 * jnp.pi) + jnp.sum(jnp.log(variances), axis=1)
        )
        log_r = jnp.log(weights) + log_norm - 0.5 * quad
        r = jax.nn.softmax(log_r, axis=-1)  # (n, k)
    with device_scope("gmm.mstep"):
        return r.sum(axis=0), _mm(r.T, X), _mm(r.T, X * X)


def _em_layout(X):
    """The layout under which the EM's sweeps run data-parallel over the
    sample's rows, or None where they run whole on every device: a sample
    that lies on a mesh more than one shard wide (row-sharded, or
    replicated as ``sample_rows`` leaves it: every shard then reads its own
    rows of its own copy) whose rows are a whole number of the reduction's
    blocks (``pad_multiple``), so that no row needs a mask."""
    sharding = getattr(X, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return None
    mesh, axis = sharding.mesh, config.data_axis
    if axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return None
    if X.shape[0] % pad_multiple(mesh.shape[axis]):
        return None
    return SpecLayout(mesh, axis)


@partial(jax.jit, static_argnames=("k", "max_iters", "layout"))
def _fit_gmm(X, key, k: int, max_iters: int, min_var: float, layout=None):
    """k-means seeds, then ``max_iters`` EM sweeps. With ``layout`` each
    sweep's sums are made a shard's rows at a time and reduced across the
    mesh by ``sharded_rowsum`` (``coll.em``); the seeding and what follows
    the sums run whole on every device, as on one."""
    n, d = X.shape

    # Init from a short k-means run.
    centers = _fit_kmeans(X, key, k, 5)
    assign = jnp.argmin(_sq_dists(X, centers), axis=1)
    onehot = jax.nn.one_hot(assign, k, dtype=X.dtype)
    counts = jnp.maximum(onehot.sum(axis=0), 1.0)
    weights0 = counts / n
    means0 = _mm(onehot.T, X) / counts[:, None]
    ex2 = _mm(onehot.T, X * X) / counts[:, None]
    vars0 = jnp.maximum(ex2 - means0**2, min_var)

    sums = _sweep_sums
    if layout is not None:
        axis, width = layout.axis, layout.num_shards
        sums = shard_map(
            lambda xb, *theta: sharded_rowsum(
                lambda x: _sweep_sums(x, *theta), axis, width, (xb,),
                scope="coll.em",
            ),
            mesh=layout.mesh, in_specs=(P(axis), P(), P(), P()),
            out_specs=P(), check_vma=False,
        )

    def em(_i, carry):
        mass, first, second = sums(X, *carry)
        with device_scope("gmm.mstep"):
            nk = jnp.maximum(mass, 1e-6)
            new_means = first / nk[:, None]
            new_ex2 = second / nk[:, None]
            new_vars = jnp.maximum(new_ex2 - new_means**2, min_var)
            return nk / n, new_means, new_vars

    return jax.lax.fori_loop(0, max_iters, em, (weights0, means0, vars0))


class GaussianMixtureModelEstimator(Estimator):
    def __init__(
        self,
        k: int,
        max_iters: int = 50,
        min_var: float = 1e-4,
        seed: int = 0,
    ):
        self.k = k
        self.max_iters = max_iters
        self.min_var = min_var
        self.seed = seed

    def fit(self, data) -> GaussianMixtureModel:
        # The k-means that seeds the EM runs inside _fit_gmm's one jitted
        # program, so it has no span of its own here.
        with span_of(active_tracer(), "gmm.fit", "featurizer",
                     bytes=upload_nbytes(data)):
            X = jnp.asarray(data, dtype=config.default_dtype)
            layout = _em_layout(X)
            if layout is not None:  # a sweep's mass and two moments
                count_reduced(layout.mesh, self.max_iters * X.dtype.itemsize
                              * self.k * (1 + 2 * X.shape[1]))
            w, m, v = _fit_gmm(
                X, jax.random.PRNGKey(self.seed), self.k, self.max_iters,
                self.min_var, layout,
            )
            return GaussianMixtureModel(w, m, v)
