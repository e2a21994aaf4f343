"""Row/column sampling helpers feeding profilers and sample-based fits
(GMM, PCA).

Ref: src/main/scala/nodes/stats/Sampler.scala, ColumnSampler [unverified].
The indices are always drawn on the host (numpy's generator, so a seed
gives the same draw whatever holds the data); the rows are gathered where
the data is: a host array with numpy, a ``jax.Array`` on its device, from
where the sample never comes to the host; rows sharded over a mesh on their
shards, the sample replicated (``_take_rows_sharded``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from keystone_tpu.linalg.row_matrix import count_reduced
from keystone_tpu.utils.mesh import layout_of_array
from keystone_tpu.utils.metrics import device_scope


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


@functools.lru_cache(maxsize=None)
def _take_rows_sharded(layout):
    """``_take_rows`` of an ``X`` whose leading axis is sharded over
    ``layout``'s mesh, the sample replicated: every shard gathers the rows
    of ``idx`` that it holds, zeros for the others, and the shards' picks
    are summed across the mesh (``coll.sample``; each row has one holder, so
    the sum is exact in any order). ``X`` itself never leaves its shard."""
    axis = layout.axis

    def local(x, idx):
        held = math.prod(x.shape[:-1])
        at = idx - lax.axis_index(axis) * held
        mine = (at >= 0) & (at < held)
        where = jnp.unravel_index(jnp.clip(at, 0, held - 1), x.shape[:-1])
        picked = x.at[where].get(
            indices_are_sorted=True, mode="promise_in_bounds")
        with device_scope("coll.sample"):
            return lax.psum(jnp.where(mine[:, None], picked, 0), axis)

    return jax.jit(shard_map(
        local, mesh=layout.mesh, in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False,
    ))


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
        idx = idx.astype(np.int32)
        layout = layout_of_array(X)
        if layout is None:
            return _take_rows(X, idx)
        count_reduced(layout.mesh, num_samples * d * X.dtype.itemsize)
        return _take_rows_sharded(layout)(X, idx)
    return X.reshape(-1, d)[idx]


def sample_columns(X, num_cols: int, seed: int = 0):
    d = X.shape[-1]
    if num_cols >= d:
        return X
    idx = np.random.default_rng(seed).choice(d, size=num_cols, replace=False)
    return X[..., np.sort(idx)]
