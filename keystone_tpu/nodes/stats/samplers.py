"""Row/column sampling helpers feeding profilers and sample-based fits
(GMM, PCA).

Ref: src/main/scala/nodes/stats/Sampler.scala, ColumnSampler [unverified].
The indices are always drawn on the host (numpy's generator, so a seed
gives the same draw whatever holds the data); the rows are gathered where
the data is: a host array with numpy, a ``jax.Array`` on its device, from
where the sample never comes to the host.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _take_rows(X, idx):
    """Rows ``idx`` (sorted, distinct, in range) of the (rows, d) view of
    ``X``, indexed where they lie: flattening (n, m, d) sets first would
    copy all of them to a new layout wherever m is not a multiple of the
    device's tile. The indices are an argument: closed over they would be a
    constant of the program, and a new program a seed."""
    where = jnp.unravel_index(idx, X.shape[:-1])
    return X.at[where].get(
        indices_are_sorted=True, unique_indices=True, mode="promise_in_bounds"
    )


def sample_rows(X, num_samples: int, seed: int = 0):
    """``num_samples`` rows of ``X`` drawn without replacement, in the order
    they have in ``X``; every row where ``X`` has no more than that. A row is
    a vector along the last axis: (n, m, d) descriptor sets are sampled as
    their (n·m, d) view, and the result is always two-dimensional."""
    d = X.shape[-1]
    n = math.prod(X.shape[:-1])
    if num_samples >= n:
        return X if X.ndim == 2 else X.reshape(-1, d)
    idx = np.sort(
        np.random.default_rng(seed).choice(n, size=num_samples, replace=False)
    )
    if isinstance(X, jax.Array):
        return _take_rows(X, idx.astype(np.int32))
    return X.reshape(-1, d)[idx]


def sample_columns(X, num_cols: int, seed: int = 0):
    d = X.shape[-1]
    if num_cols >= d:
        return X
    idx = np.random.default_rng(seed).choice(d, size=num_cols, replace=False)
    return X[..., np.sort(idx)]
