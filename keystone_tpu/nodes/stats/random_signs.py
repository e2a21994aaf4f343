"""Random sign flip node.

Ref: src/main/scala/nodes/stats/RandomSignNode.scala — elementwise multiply
by a fixed random ±1 vector (the "D" matrix of Fastfood-style random
features) [unverified].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from keystone_tpu.workflow import Transformer


class RandomSignNode(Transformer):
    # The signs are an argument of the program, not a constant in it.
    array_fields = ("signs",)

    def __init__(self, signs: jax.Array):
        self.signs = jnp.asarray(signs)

    @classmethod
    def create(cls, dim: int, seed: int = 0) -> "RandomSignNode":
        key = jax.random.PRNGKey(seed)
        signs = jax.random.rademacher(key, (dim,), dtype=jnp.float32)
        node = cls(signs)
        node._sig = node.stable_signature(dim, seed)
        return node

    def apply_batch(self, X):
        return X * self.signs
