"""Random Fourier features: cos(XW + b).

Ref: src/main/scala/nodes/stats/CosineRandomFeatures.scala — W drawn
Gaussian (RBF kernel) or Cauchy (Laplacian kernel), b uniform in [0, 2π);
the TIMIT pipeline's featurizer (BASELINE.json) [unverified].

The projection is one large MXU gemm; gamma scales the kernel bandwidth.
W and b are arguments of the jitted program, not constants in it
(``array_fields``): at TIMIT's width W is 0.36 GB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.config import config
from keystone_tpu.workflow import Transformer


def _draw(key, input_dim: int, num_features: int, distribution: str, dtype):
    """(W, b) of one cosine block, W before the bandwidth scale."""
    kw, kb = jax.random.split(key)
    sample = jax.random.normal if distribution == "gaussian" else jax.random.cauchy
    W = sample(kw, (input_dim, num_features), dtype=dtype)
    b = jax.random.uniform(
        kb, (num_features,), minval=0.0, maxval=2 * np.pi, dtype=dtype
    )
    return W, b


@functools.partial(
    jax.jit,
    static_argnames=("input_dim", "block_features", "blocks", "distribution", "dtype"),
)
def _draw_blocks(key, *, input_dim, block_features, blocks, distribution, dtype):
    """``blocks`` cosine blocks in one program, block ``i`` drawn from
    ``fold_in(key, i)``, laid side by side in block order."""
    W, b = jax.vmap(
        lambda i: _draw(jax.random.fold_in(key, i), input_dim, block_features,
                        distribution, dtype)
    )(jnp.arange(blocks))
    return jnp.moveaxis(W, 0, 1).reshape(input_dim, -1), b.reshape(-1)


class CosineRandomFeatures(Transformer):
    array_fields = ("W", "b")

    def __init__(self, W: jax.Array, b: jax.Array):
        self.W = jnp.asarray(W)
        self.b = jnp.asarray(b)

    @classmethod
    def create(
        cls,
        input_dim: int,
        num_features: int,
        gamma: float = 1.0,
        distribution: str = "gaussian",
        seed: int = 0,
        blocks: int = 1,
    ) -> "CosineRandomFeatures":
        """``blocks`` > 1 is upstream's gather of that many cosine nodes as
        one projection: ``num_features`` columns in ``blocks`` equal blocks,
        each with a draw of its own (block ``i`` from the seed folded with
        ``i``), in block order."""
        if distribution not in ("gaussian", "cauchy"):
            raise ValueError(f"unknown distribution {distribution!r}")
        if blocks < 1 or num_features % blocks:
            raise ValueError(
                f"{num_features} features do not divide into {blocks} blocks"
            )
        key = jax.random.PRNGKey(seed)
        dtype = config.default_dtype
        if blocks == 1:
            W, b = _draw(key, input_dim, num_features, distribution, dtype)
        else:
            W, b = _draw_blocks(
                key, input_dim=input_dim, block_features=num_features // blocks,
                blocks=blocks, distribution=distribution, dtype=dtype,
            )
        node = cls(W * gamma, b)
        # dtype is part of the identity: the drawn W/b values depend on it.
        node._sig = node.stable_signature(
            input_dim, num_features, gamma, distribution, seed, str(dtype),
            blocks,
        )
        return node

    def apply_batch(self, X):
        # HIGHEST: at the TPU default (one bf16 pass) the phase X W is off
        # by 1e-2 of a radian and the features with it; float32 features
        # need the float32 product.
        return jnp.cos(
            jnp.matmul(X, self.W, precision=jax.lax.Precision.HIGHEST) + self.b
        )
