"""Row-sharded tall-skinny distributed matrix.

Ref: ml-matrix `RowPartitionedMatrix` / `DistributedMatrix` (SURVEY.md §2.2)
[unverified]. An ``RDD[RowPartition(DenseMatrix)]`` becomes a single device
array sharded on its leading axis over the mesh's ``data`` axis; rows are
zero-padded to a multiple of the shard count (zero rows are invisible to the
gram/normal-equation reductions, and `collect` strips them).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from keystone_tpu.config import config
from keystone_tpu.utils.metrics import device_scope, program_counters
from keystone_tpu.utils.mesh import (
    default_mesh,
    fold_blocks,
    pad_multiple,
    pad_rows,
)


def _precision():
    return {
        "highest": lax.Precision.HIGHEST,
        "high": lax.Precision.HIGH,
        "default": lax.Precision.DEFAULT,
    }[config.solver_precision]


def storage_dtype():
    """Dtype for the solver's big operands (config.solver_storage_dtype)."""
    return jnp.dtype(config.solver_storage_dtype or config.default_dtype)


def donate_argnums(mesh: Mesh, *argnums: int):
    """donate_argnums for the solver hot loops on real hardware: the old
    residual/weight/accumulator buffers are dead the moment the update
    returns, and donating them caps the solver's HBM high-water at one live
    copy (SURVEY.md §5 sanitizer row's donation/aliasing prescription).
    Gated on ``config.donate_buffers`` (KEYSTONE_DONATE_BUFFERS=0 pins the
    non-donated baseline for A/B benches and deleted-buffer debugging).
    CPU meshes keep the legacy refusal: these loops predate runtimes that
    honor host donation, and their CPU test surface pins the undonated
    lowering — the workflow layer's staged-chain donation
    (``SpecLayout.jit``) is the path that donates on every backend."""
    if not config.donate_buffers:
        return ()
    if mesh.devices.flat[0].platform == "cpu":
        return ()
    return argnums


def solver_matmul(x, y, precision):
    """Matmul on the solver path, dtype-aware.

    When either operand is stored in bfloat16 (the throughput mode), both
    are fed to the MXU as bf16 with f32 accumulation — its native fast path
    (one pass, full accumulator width). Full-width operands keep the
    configured solver precision (HIGHEST = 6-pass bf16 emulation of f32).
    """
    if x.dtype == jnp.bfloat16 or y.dtype == jnp.bfloat16:
        return jnp.matmul(
            x.astype(jnp.bfloat16),
            y.astype(jnp.bfloat16),
            preferred_element_type=jnp.dtype(config.accum_dtype),
        )
    return jnp.matmul(x, y, precision=precision)


def count_reduced(mesh: Mesh, nbytes: int) -> None:
    """Add ``nbytes`` to ``program_counters``' ``collective_bytes``: what a
    reduction over ``mesh``'s data axis is handed, from shapes, on the host
    that dispatches it (a second fit traces nothing, so nothing inside a
    program can count). The size of the array that is summed, once,
    whatever the exchange's algorithm sends; nothing on a mesh one wide,
    where nothing crosses."""
    if mesh.shape[config.data_axis] > 1:
        program_counters.bump("collective_bytes", int(nbytes))


def sharded_rowsum(block_fn, axis: str, width: int, operands, row_axes=None,
                   *, scope: str):
    """THE reduction over the sharded row axis for every solver
    accumulator (grams, AᵀB, column sums) — call inside a shard_map body.
    What crosses the mesh (the butterfly's exchanges and adds, or the
    ``psum``) runs under ``device_scope(scope)``, one of the ``coll.``
    names of ``DEVICE_SCOPES``; the partial sums a shard makes alone stay
    in the caller's scope.

    ``block_fn(*row_slices)`` maps row slices of ``operands`` to a pytree
    of partial sums. With the canonical fold active
    (``utils.mesh.fold_blocks``), the logical rows are cut into a FIXED
    number of blocks — the same blocks on every mesh width, because rows
    pad to a multiple of the block count (``pad_multiple``) — and the
    per-block partials combine in a balanced binary tree: local subtrees
    per shard, then a butterfly (log₂ width ppermute rounds) across them.
    Every width that divides the block count therefore sums in the SAME
    order and produces the SAME bits — the invariance the elastic mesh
    resume gate (reshard then continue, bit-identical to a fresh fit at
    the new width) stands on. Widths outside the fold's reach keep the
    legacy whole-shard ``psum`` (order differs per width, sums still
    exact). ``row_axes`` names the row axis per operand (default 0 — the
    batched-gram callers reduce over axis 1 of a stacked operand)."""
    if row_axes is None:
        row_axes = (0,) * len(operands)
    C = fold_blocks(width)
    if not C:
        local = block_fn(*operands)
        with device_scope(scope):
            return jax.tree_util.tree_map(lambda v: lax.psum(v, axis), local)
    blocks_per_shard = C // width
    parts = []
    for i in range(blocks_per_shard):
        slices = [
            lax.slice_in_dim(
                op,
                i * (op.shape[ra] // blocks_per_shard),
                (i + 1) * (op.shape[ra] // blocks_per_shard),
                axis=ra,
            )
            for op, ra in zip(operands, row_axes)
        ]
        parts.append(block_fn(*slices))
    while len(parts) > 1:
        parts = [
            jax.tree_util.tree_map(jnp.add, parts[i], parts[i + 1])
            for i in range(0, len(parts), 2)
        ]
    acc = parts[0]
    step = 1
    with device_scope(scope):
        while step < width:
            perm = [(i, i ^ step) for i in range(width)]
            acc = jax.tree_util.tree_map(
                lambda v, p=perm: v + lax.ppermute(v, axis, p), acc
            )
            step *= 2
    return acc


@lru_cache(maxsize=None)
def _gram_fn(mesh: Mesh, axis: str, precision, fold: int):
    width = mesh.shape[axis]

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False)
    def gram(a):
        return sharded_rowsum(
            lambda ab: solver_matmul(ab.T, ab, precision), axis, width, (a,),
            scope="coll.gram",
        )

    return gram


@lru_cache(maxsize=None)
def _atb_fn(mesh: Mesh, axis: str, precision, fold: int):
    width = mesh.shape[axis]

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(), check_vma=False)
    def atb(a, b):
        return sharded_rowsum(
            lambda ab, bb: solver_matmul(ab.T, bb, precision),
            axis, width, (a, b), scope="coll.atr",
        )

    return atb


@lru_cache(maxsize=None)
def _gram_and_atb_fn(mesh: Mesh, axis: str, precision, fold: int):
    width = mesh.shape[axis]

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=(P(), P()), check_vma=False)
    def gram_and_atb(a, b):
        # One program: a is read from HBM once for both reductions.
        return sharded_rowsum(
            lambda ab, bb: (
                solver_matmul(ab.T, ab, precision),
                solver_matmul(ab.T, bb, precision),
            ),
            axis, width, (a, b), scope="coll.gram",
        )

    return gram_and_atb


@lru_cache(maxsize=None)
def _col_sum_fn(mesh: Mesh, axis: str, fold: int):
    width = mesh.shape[axis]

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False)
    def col_sum(a):
        return sharded_rowsum(
            lambda ab: jnp.sum(ab, axis=0), axis, width, (a,),
            scope="coll.moments",
        )

    return col_sum


@lru_cache(maxsize=None)
def _weighted_col_sum_fn(mesh: Mesh, axis: str, fold: int):
    width = mesh.shape[axis]

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(), check_vma=False)
    def weighted_col_sum(w, a):
        return sharded_rowsum(
            lambda wb, ab: jnp.sum(wb * ab, axis=0), axis, width, (w, a),
            scope="coll.moments",
        )

    return weighted_col_sum


@lru_cache(maxsize=None)
def _matmul_fn(mesh: Mesh, axis: str, precision):
    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis))
    def mm(a, w):
        return solver_matmul(a, w, precision)

    return mm


def _width(matrix: "RowMatrix") -> int:
    """A row's width: 1 for the vector a one-column B may be."""
    return int(np.prod(matrix.data.shape[1:], dtype=np.int64))


class RowMatrix:
    """An (n, d) matrix stored row-sharded over the mesh ``data`` axis.

    ``data`` has shape (n_padded, d) with ``n_padded % num_shards == 0``;
    ``n`` is the logical row count.
    """

    def __init__(self, data: jax.Array, n: int, mesh: Mesh):
        self.data = data
        self.n = int(n)
        self.mesh = mesh

    # -- construction ------------------------------------------------------

    @classmethod
    def from_array(
        cls,
        x,
        mesh: Optional[Mesh] = None,
        dtype=None,
    ) -> "RowMatrix":
        mesh = mesh or default_mesh()
        axis = config.data_axis
        k = mesh.shape[axis]
        dtype = dtype or config.default_dtype
        x = np.asarray(x, dtype=dtype) if isinstance(x, np.ndarray) else jnp.asarray(x, dtype=dtype)
        # pad_multiple, not the raw width: with the canonical fold active
        # every mesh width pads (and blocks) rows identically, which is
        # what makes the gram fold — and thus whole solves —
        # bit-identical across widths (the elastic-mesh resume gate).
        padded, n = pad_rows(x, pad_multiple(k))
        sharding = NamedSharding(mesh, P(axis))
        data = jax.device_put(padded, sharding)
        return cls(data, n, mesh)

    # -- properties --------------------------------------------------------

    @property
    def shape(self):
        return (self.n, self.data.shape[1])

    @property
    def padded_rows(self) -> int:
        return self.data.shape[0]

    @property
    def num_shards(self) -> int:
        return self.mesh.shape[config.data_axis]

    # -- ops ---------------------------------------------------------------

    def collect(self) -> np.ndarray:
        """Gather to host, stripping padding (the RDD ``collect`` analog)."""
        return np.asarray(self.data)[: self.n]

    def gram(self) -> jax.Array:
        """AᵀA, replicated: per-shard MXU gemm + psum over ICI
        (the ``treeAggregate`` of local grams in NormalEquations)."""
        d = _width(self)
        count_reduced(self.mesh, d * d * self.data.dtype.itemsize)
        return _gram_fn(
            self.mesh, config.data_axis, _precision(),
            fold_blocks(self.num_shards),
        )(self.data)

    def atb(self, other: "RowMatrix") -> jax.Array:
        """AᵀB for a row-aligned B."""
        self._check_aligned(other)
        count_reduced(self.mesh, _width(self) * _width(other)
                      * self.data.dtype.itemsize)
        return _atb_fn(
            self.mesh, config.data_axis, _precision(),
            fold_blocks(self.num_shards),
        )(self.data, other.data)

    def gram_and_atb(self, other: "RowMatrix"):
        """(AᵀA, AᵀB) in one fused program — A is read once."""
        self._check_aligned(other)
        d = _width(self)
        count_reduced(self.mesh, d * (d + _width(other))
                      * self.data.dtype.itemsize)
        return _gram_and_atb_fn(
            self.mesh, config.data_axis, _precision(),
            fold_blocks(self.num_shards),
        )(self.data, other.data)

    def col_sums(self) -> jax.Array:
        """Column sums over the LOGICAL rows, replicated: per-shard sum +
        psum over ICI. Zero pad rows are inert, so this equals the
        unpadded sum — and because every construction path re-shards onto
        the same mesh, the result is bit-identical no matter what
        placement the source array arrived with (the property that keeps
        intercept means — and thus whole fits — placement-invariant)."""
        count_reduced(self.mesh, _width(self) * self.data.dtype.itemsize)
        return _col_sum_fn(
            self.mesh, config.data_axis, fold_blocks(self.num_shards)
        )(self.data)

    def weighted_col_sums(self, weights: "RowMatrix") -> jax.Array:
        """Σ_i w_i · row_i for a row-aligned (n, 1) weight column — the
        weighted-centering reduction, psum'd like ``col_sums``."""
        self._check_aligned(weights)
        count_reduced(self.mesh, _width(self) * self.data.dtype.itemsize)
        return _weighted_col_sum_fn(
            self.mesh, config.data_axis, fold_blocks(self.num_shards)
        )(weights.data, self.data)

    def centered(self, means: jax.Array, dtype=None) -> "RowMatrix":
        """``self - means`` over the LOGICAL rows, pad rows kept ZERO (a
        plain subtraction would turn them into ``-means`` and poison the
        gram-inertness contract), optionally cast to the solver storage
        dtype. Derived on-device from the already-sharded data, so
        intercept centering costs ZERO additional host-to-device
        transfers of the big operand — the subtraction/mask/cast are
        elementwise and placement-inert, keeping centered fits
        bit-identical across arrival placements."""
        mask = (jnp.arange(self.padded_rows) < self.n)[:, None]
        data = jnp.where(mask, self.data - means, 0)
        if dtype is not None:
            data = data.astype(dtype)
        return RowMatrix(data, self.n, self.mesh)

    def matmul(self, w: jax.Array) -> "RowMatrix":
        """A @ W for replicated W; result stays row-sharded."""
        out = _matmul_fn(self.mesh, config.data_axis, _precision())(
            self.data, jnp.asarray(w, dtype=self.data.dtype)
        )
        return RowMatrix(out, self.n, self.mesh)

    def cols(self, start: int, stop: int) -> "RowMatrix":
        """Column block view (feature-block parallelism's unit of work)."""
        return RowMatrix(self.data[:, start:stop], self.n, self.mesh)

    def _check_aligned(self, other: "RowMatrix") -> None:
        if (
            other.padded_rows != self.padded_rows
            or other.n != self.n
            or other.mesh is not self.mesh
        ):
            raise ValueError(
                "row-matrices must share n, padding, and mesh "
                f"(got {self.shape}/{self.padded_rows} vs {other.shape}/{other.padded_rows})"
            )
