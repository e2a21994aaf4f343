"""Mean/std standardization estimator.

Ref: src/main/scala/nodes/stats/StandardScaler.scala — fit computes column
mean (and optionally std); the model subtracts/divides [unverified].
"""

from __future__ import annotations

import jax.numpy as jnp

from keystone_tpu.workflow import Estimator, Transformer


class StandardScalerModel(Transformer):
    array_fields = ("mean", "std")

    def __init__(self, mean, std=None):
        self.mean = jnp.asarray(mean)
        self.std = None if std is None else jnp.asarray(std)

    def apply_batch(self, X):
        out = X - self.mean
        if self.std is not None:
            out = out / self.std
        return out


class StandardScaler(Estimator):
    def __init__(self, normalize_std_dev: bool = True, eps: float = 1e-8):
        self.normalize_std_dev = normalize_std_dev
        self.eps = eps

    def fit(self, data) -> StandardScalerModel:
        X = jnp.asarray(data)
        mean = X.mean(axis=0)
        std = None
        if self.normalize_std_dev:
            std = jnp.maximum(X.std(axis=0, ddof=1), self.eps)
        return StandardScalerModel(mean, std)
