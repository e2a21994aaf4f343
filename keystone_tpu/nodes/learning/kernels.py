"""Kernel generators.

Ref: src/main/scala/nodes/learning/KernelMatrix.scala /
GaussianKernelGenerator (SURVEY.md §2.4 kernel ridge row) [unverified].
A kernel generator produces gemm-shaped kernel blocks on demand — the
KernelMatrix of the reference becomes block computation fused into the
consumer, never an n×n array in memory.

A generator is a pytree, as every ``Transformer`` is: its parameters
(``param_fields``: the Gaussian's ``gamma``) are the children, its class the
static part. A program that is handed one takes the parameters as arguments,
so every generator of a class shares it, whatever its values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def pairwise_sq_dists(X, Z, precision=None):
    """||x − z||² for all pairs, gemm-shaped (MXU-friendly), clamped ≥ 0
    against cancellation. The single source of truth for this expansion.
    ``precision`` is the product's (None: the backend's default, as
    k-means and the conjugate-gradient solver take it)."""
    sq = (
        jnp.sum(X * X, axis=1, keepdims=True)
        - 2.0 * jnp.matmul(X, Z.T, precision=precision)
        + jnp.sum(Z * Z, axis=1)
    )
    return jnp.maximum(sq, 0.0)


class KernelGenerator:
    # The attributes that hold the kernel's parameters: float32 scalars,
    # arguments of whatever program is handed the generator.
    param_fields: tuple = ()

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        jax.tree_util.register_pytree_node(
            cls,
            lambda k: (tuple(getattr(k, f) for f in cls.param_fields), None),
            lambda _aux, params: cls._of(params),
        )

    @classmethod
    def _of(cls, params) -> "KernelGenerator":
        made = object.__new__(cls)
        made.__dict__.update(zip(cls.param_fields, params))
        return made

    def block(self, X, Z):
        """Kernel block k(X, Z) of shape (len(X), len(Z))."""
        raise NotImplementedError


class GaussianKernelGenerator(KernelGenerator):
    """k(x, z) = exp(−gamma ||x − z||²)."""

    param_fields = ("gamma",)

    def __init__(self, gamma: float):
        self.gamma = np.float32(gamma)

    def block(self, X, Z):
        # HIGHEST, as the solvers' products: the squared norms the product
        # is taken from are float32 sums.
        return jnp.exp(-self.gamma * pairwise_sq_dists(X, Z, lax.Precision.HIGHEST))


class LinearKernelGenerator(KernelGenerator):
    def block(self, X, Z):
        return jnp.matmul(X, Z.T, precision=lax.Precision.HIGHEST)
