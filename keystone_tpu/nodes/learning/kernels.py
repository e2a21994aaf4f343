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


def pairwise_sq_dists(X, Z, precision=None, symmetric=False):
    """||x − z||² for all pairs, gemm-shaped (MXU-friendly), clamped ≥ 0
    against cancellation. The single source of truth for this expansion.
    ``precision`` is the product's (None: the backend's default, as
    k-means and the conjugate-gradient solver take it). ``symmetric`` adds
    the two norms first, so the pair (x, z) and the pair (z, x) round alike
    wherever the product does: a kernel block's rows of another block are
    then that block's rows of it, transposed, to the bit."""
    sx = jnp.sum(X * X, axis=1, keepdims=True)
    xz = 2.0 * jnp.matmul(X, Z.T, precision=precision)
    if symmetric:
        sq = (sx + jnp.sum(Z * Z, axis=1)) - xz
    else:
        sq = sx - xz + jnp.sum(Z * Z, axis=1)
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
        # is taken from are float32 sums. Symmetric: the kernel solver
        # copies K[B_t, B] from K[B, B_t] where it holds that block.
        return jnp.exp(-self.gamma * pairwise_sq_dists(
            X, Z, lax.Precision.HIGHEST, symmetric=True))


class LinearKernelGenerator(KernelGenerator):
    def block(self, X, Z):
        return jnp.matmul(X, Z.T, precision=lax.Precision.HIGHEST)
