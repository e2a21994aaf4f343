"""Tolerant numeric comparison — the backbone of the reference's test suite —
and the row normalisation of the CIFAR patch pipelines.

Ref: src/main/scala/utils/Stats.scala `aboutEq`, `normalizeRows` [unverified].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def normalize_rows(X, alpha=1.0):
    """Each row less its mean, over sqrt(its variance + ``alpha``), the
    variance divided by the row's length less one
    (`Stats.normalizeRows(mat, alpha)`)."""
    centred = X - X.mean(axis=1, keepdims=True)
    var = (centred * centred).sum(axis=1, keepdims=True) / (X.shape[1] - 1)
    return centred / jnp.sqrt(var + alpha)


def about_eq(a, b, tol: float = 1e-6) -> bool:
    """True if every element of |a - b| is within tol (absolute).

    Mirrors `Stats.aboutEq(a, b, tol)`. Accepts scalars, arrays, or nested
    sequences; uses max-abs difference like the reference.
    """
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if a.shape != b.shape:
        return False
    return bool(np.max(np.abs(a - b), initial=0.0) <= tol)
