"""Block coordinate descent over feature blocks — the reference's workhorse
solver for 64k–256k-dim featurized problems.

Ref: ml-matrix `BlockCoordinateDescent` driving
`BlockLeastSquaresEstimator.fit` (SURVEY.md §3.2) [unverified]:

    for epoch; for block b:
        residual update: R ← R + A_b W_b       [per-partition gemm]
        gram/gradient via treeAggregate        [the comm bottleneck]
        driver Cholesky solve → broadcast W_b

TPU lowering (the SURVEY's north-star stack): the per-partition gemms are
per-chip MXU matmuls on the row-sharded A_b and residual; `treeAggregate`
becomes `psum` over ICI; the (b, b) Cholesky solve runs replicated on every
chip (no driver hop, no broadcast — the result is already everywhere).

Supports per-row weights for the class-balanced ImageNet variant
(Ref: BlockWeightedLeastSquaresEstimator [unverified]).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.scipy.linalg import solve_triangular
from jax.sharding import Mesh, PartitionSpec as P

from keystone_tpu.config import config
from keystone_tpu.utils.mesh import fold_blocks, register_reshard_adapter
from keystone_tpu.utils.metrics import device_scope
from keystone_tpu.linalg.row_matrix import (
    RowMatrix,
    _blocks_kept,
    _precision,
    count_reduced,
    donate_argnums as _donate,
    keep_block,
    read_or_make,
    sharded_rowsum,
    solver_matmul,
    storage_dtype,
)


# -- shared per-shard solver math (single source for every shard_map body) --


def _local_weighted(a_b, w_rows, weighted: bool):
    with device_scope("solver.update"):
        return a_b * w_rows[:, None] if weighted else a_b


def _local_gram_inv(a_b, aw, lam, precision, axis, width):
    """Explicit ridge resolvent (AᵀA + λI)⁻¹ for the block, the gram
    reduced over the sharded rows in the canonical width-independent fold
    (``sharded_rowsum`` — the elastic-mesh bit-identity contract). ``lam``
    is a scalar, or the block's (1, b) ridge diagonal (``_pad_ridge``).

    The inverse — not the Cholesky factor — is the cached quantity: XLA
    lowers triangular solves to a sequential substitution that dominates
    BCD wall-clock on TPU, while multiplying by a precomputed inverse is
    one MXU gemm. Forming the inverse costs, once per block, a Cholesky and
    the blocked triangular inverse and product of ``_batched_spd_inv``;
    the λ-regularized SPD gram keeps it well-conditioned, and later epochs
    re-solve against the residual, so per-epoch solve error self-corrects
    instead of accumulating."""
    with device_scope("solver.gram"):
        gram = sharded_rowsum(
            lambda awb, ab: solver_matmul(awb.T, ab, precision),
            axis, width, (aw, a_b), scope="coll.gram",
        )
        b = a_b.shape[1]
        gram = gram + lam * jnp.eye(b, dtype=gram.dtype)
    return _batched_spd_inv(gram)


def _local_solve_update(a_b, aw, inv, r, w_b, precision, axis, width):
    with device_scope("solver.update"):
        r_plus = r + solver_matmul(a_b, w_b, precision)
        rhs = sharded_rowsum(
            lambda awb, rb: solver_matmul(awb.T, rb, precision),
            axis, width, (aw, r_plus), scope="coll.atr",
        )
        w_b_new = solver_matmul(inv, rhs, precision)
        r_new = r_plus - solver_matmul(a_b, w_b_new, precision)
    return r_new, w_b_new


# Widest triangle the blocked inverse hands to XLA's own triangular solve
# and to one dense YᵀY. Chosen on a v5e among 512, 1024 and 2048: the
# inverse of (2, 8192, 8192) in 83.1, 81.7, 81.9 ms and of (8, 4096, 4096)
# in 54.9, 53.5, 62.0 ms, where the dense solve and product took 196.4 and
# 108.8 (chip run, PR 29, call 81: PERF.md section 6). A constant, not a
# knob.
_INV_LEAF = 1024


def _mm(x, y):
    return jnp.matmul(x, y, precision=lax.Precision.HIGHEST)


def _inv_split(b: int) -> int:
    """Where a (b, b) triangle is cut in two: the multiple of the 128-lane
    tile nearest b/2, so that the tiles start on a tile boundary; the plain
    half where b is too small for that."""
    h = max(128, (b + 128) // 256 * 128)
    return h if h < b else b // 2


def _inv_levels(b: int, leaf: int = _INV_LEAF) -> int:
    """How many times the blocked inverse cuts a (b, b) block before every
    piece is at most ``leaf`` wide: 0 is one leaf, the unblocked path."""
    if b <= leaf:
        return 0
    h = _inv_split(b)
    return 1 + max(_inv_levels(h, leaf), _inv_levels(b - h, leaf))


def _tri_inv(chol, leaf: int):
    """Y = L⁻¹ of a (batched) lower-triangular L, LAPACK's ``trtri`` by
    halves: Y11 = inv(L11), Y22 = inv(L22), Y21 = −Y22 (L21 Y11). The
    upper-right tile is zero and never computed, and all but the leaves'
    2·leaf³ is two MXU gemms a level: 2b³/3 FLOP where a solve against the
    whole identity is 2b³ in XLA's unrolled 128-row panel chain."""
    b = chol.shape[-1]
    if b <= leaf:
        eye = jnp.broadcast_to(jnp.eye(b, dtype=chol.dtype), chol.shape)
        return solve_triangular(chol, eye, lower=True)
    h = _inv_split(b)
    y11 = _tri_inv(chol[..., :h, :h], leaf)
    y22 = _tri_inv(chol[..., h:, h:], leaf)
    y21 = -_mm(y22, _mm(chol[..., h:, :h], y11))
    return jnp.block([[y11, jnp.zeros_like(y21.mT)], [y21, y22]])


def _tri_gram(y, leaf: int):
    """YᵀY of a (batched) lower-triangular Y, LAPACK's ``lauum`` by halves
    (the same cut as ``_tri_inv``): S11 = gram(Y11) + Y21ᵀY21,
    S21 = Y22ᵀY21, S22 = gram(Y22), S12 = S21ᵀ. 2b³/3 FLOP where the dense
    product multiplies 2b³, two thirds of them by zeros."""
    b = y.shape[-1]
    if b <= leaf:
        return _mm(y.mT, y)
    h = _inv_split(b)
    y21, y22 = y[..., h:, :h], y[..., h:, h:]
    s11 = _tri_gram(y[..., :h, :h], leaf) + _mm(y21.mT, y21)
    s21 = _mm(y22.mT, y21)
    return jnp.block([[s11, s21.mT], [s21, _tri_gram(y22, leaf)]])


def _batched_spd_inv(grams, leaf: int = _INV_LEAF):
    """(Batched) SPD inverse — THE single source for the factor-phase
    inverse, batched (leading block axis) or not: LAPACK's ``potri`` shape,
    A = LLᵀ, Y = L⁻¹ (``_tri_inv``), A⁻¹ = YᵀY (``_tri_gram``), every
    product float32 at ``HIGHEST``.

    Both steps skip the zeros of the triangle, 4b³/3 FLOP a block where a
    dense solve against the identity and a dense YᵀY run 4b³. It adapts on
    b alone: a block at most ``_INV_LEAF`` wide (every toy width) is one
    leaf, one ``solve_triangular`` and one gemm. The leaves also bound the
    unrolled trsm expansion's temporaries (batch·leaf³·itemsize/128: 0.27 GB
    at batch 8, leaf 1024), which at b = 8192 against the whole identity
    failed v5e buffer assignment. ``leaf`` is for tests."""
    assert leaf >= 1, f"leaf must be >= 1, got {leaf}"
    with device_scope("solver.cholesky"):
        chol = jnp.linalg.cholesky(grams)
    with device_scope("solver.inverse"):
        return _tri_gram(_tri_inv(chol, leaf), leaf)


def _pad_ridge(lam, nb: int, b: int, pad: int):
    """The ridge diagonals of ``nb`` stacked blocks whose last ends in
    ``pad`` zero columns: λ on every real column and 1 on the pad, so the
    padded gram stays positive definite at λ = 0 and, being block diagonal,
    its inverse holds the real gram's inverse and the pad columns' weights
    stay exactly 0. (nb, 1, b): times ``eye(b)`` it is each block's
    diagonal, as the scalar λ is."""
    return jnp.full((nb, 1, b), lam).at[-1, 0, b - pad:].set(1)


@lru_cache(maxsize=None)
def _stack_blocks_fn(mesh: Mesh, axis: str, nb: int, pad: int = 0):
    """(rows, d) → (nb, rows, (d + pad)/nb) stacked equal-size column
    blocks, in one program: the one extra copy of A the solve makes, laid
    out so a `lax.scan` can carry the epoch loop over the leading block
    axis. ``pad`` zero columns fill a ragged last block in the same copy
    (0, and the program without them, where the blocks tile d)."""

    def local(a):
        with device_scope("solver.stack"):
            if pad:
                a = jnp.pad(a, ((0, 0), (0, pad)))
            r, d = a.shape
            return jnp.moveaxis(a.reshape(r, nb, d // nb), 1, 0)

    sm = shard_map(
        local,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(None, axis),
        check_vma=False,
    )
    return jax.jit(sm)


@lru_cache(maxsize=None)
def _fused_factor_fn(mesh: Mesh, axis: str, precision, weighted: bool,
                     fold: int, pad: int = 0):
    """A chunk of blocks' ridge inverses in ONE program: batched
    canonical-fold grams (one big MXU batch-gemm per row block) into
    ``_batched_spd_inv``: one dispatch per chunk of blocks in place of one
    per block. ``pad``: the chunk's last block ends in that many zero
    columns (``_pad_ridge``)."""
    width = mesh.shape[axis]

    def local(a3, lam, w_rows):  # a3: (chunk, rows_shard, b)
        with device_scope("solver.gram"):
            aw = a3 * w_rows[None, :, None] if weighted else a3
            gram = sharded_rowsum(
                lambda awb, ab: solver_matmul(
                    jnp.swapaxes(awb, 1, 2), ab, precision
                ),
                axis, width, (aw, a3), row_axes=(1, 1), scope="coll.gram",
            )
            b = a3.shape[2]
            if pad:
                lam = _pad_ridge(lam, a3.shape[0], b, pad)
            gram = gram + lam * jnp.eye(b, dtype=gram.dtype)
        return _batched_spd_inv(gram)

    sm = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, axis), P(), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sm)


@lru_cache(maxsize=None)
def _fused_epochs_fn(
    mesh: Mesh, axis: str, precision, weighted: bool, num_epochs: int,
    cached: bool, fold: int, pad: int = 0,
):
    """The whole multi-epoch BCD sweep as ONE XLA program: scan over blocks
    inside scan over epochs, per-shard under shard_map. The solve is a
    single launch whatever nb·epochs, XLA pipelines the scan body's gemms
    back-to-back on the MXU, and the collective schedule is fixed at
    compile time (so the CPU's in-process rendezvous cannot deadlock on
    it). What one dispatch a block visit would cost on a TPU is not
    measured.

    ``cached=True`` consumes precomputed ridge inverses (xs carries them);
    ``cached=False`` forms gram and inverse again at every block visit: the
    single-epoch mode, and the one where the inverses do not fit. Only the
    uncached body reads ``pad``, the zero columns that end the last block
    (``_pad_ridge``)."""
    width = mesh.shape[axis]

    def local(a3, invs, r, w3, lam, w_rows):
        ridge = (_pad_ridge(lam, a3.shape[0], a3.shape[2], pad),) if pad else ()

        def block_step(rc, xs):
            a_b, inv, w_b = xs[:3]
            aw = _local_weighted(a_b, w_rows, weighted)
            if not cached:
                inv = _local_gram_inv(
                    a_b, aw, xs[3] if pad else lam, precision, axis, width
                )
            r_new, w_new = _local_solve_update(
                a_b, aw, inv, rc, w_b, precision, axis, width
            )
            return r_new, w_new

        def epoch_step(carry, _):
            rc, w3c = carry
            rc, w3c = lax.scan(block_step, rc, (a3, invs, w3c) + ridge)
            return (rc, w3c), None

        # The scans' own slices and stacked results belong to the update;
        # the uncached body's gram, Cholesky and inverse are scopes inside.
        with device_scope("solver.update"):
            (r, w3), _ = lax.scan(
                epoch_step, (r, w3), None, length=num_epochs)
        return r, w3

    sm = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, axis), P(), P(axis), P(), P(), P(axis)),
        out_specs=(P(axis), P()),
        check_vma=False,
    )
    return jax.jit(sm, donate_argnums=_donate(mesh, 2, 3))


@lru_cache(maxsize=None)
def _cached_block_update_fn(mesh: Mesh, axis: str, precision,
                            weighted: bool, fold: int):
    """BCD block update reusing the precomputed ridge inverse: only MXU
    gemms remain in the epoch loop — the dominant 2·n·b² gram FLOPs drop
    out after the first epoch, and no triangular solve ever runs in it."""
    width = mesh.shape[axis]

    def local(a_b, inv, r, w_b, w_rows):
        aw = _local_weighted(a_b, w_rows, weighted)
        return _local_solve_update(
            a_b, aw, inv, r, w_b, precision, axis, width
        )

    sm = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(), P(axis)),
        out_specs=(P(axis), P()),
        check_vma=False,
    )
    return jax.jit(sm, donate_argnums=_donate(mesh, 2, 3))


@lru_cache(maxsize=None)
def _first_epoch_update_fn(mesh: Mesh, axis: str, precision,
                           weighted: bool, fold: int):
    """Fused block update that also emits the gram's ridge inverse — the
    streamed path's first epoch. Fusion keeps a_b in one XLA program so the
    block is read from HBM once for gram + update instead of twice."""
    width = mesh.shape[axis]

    def local(a_b, r, w_b, lam, w_rows):
        aw = _local_weighted(a_b, w_rows, weighted)
        inv = _local_gram_inv(a_b, aw, lam, precision, axis, width)
        r_new, w_b_new = _local_solve_update(
            a_b, aw, inv, r, w_b, precision, axis, width
        )
        return r_new, w_b_new, inv

    sm = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P(axis)),
        out_specs=(P(axis), P(), P()),
        check_vma=False,
    )
    return jax.jit(sm, donate_argnums=_donate(mesh, 1, 2))


def _factor_chunk(block_size: Optional[int] = None) -> int:
    """Blocks factorized per batched XLA program. Batching amortizes TPU's
    sequential factorization lowering, but measured 2.3× slower than
    independent per-block programs on the CPU backend — there, per-block.

    The chunk is additionally MEMORY-capped: the factor program holds
    a handful of (chunk, b, b) temps (the gram, its Cholesky factor, the
    halves of the blocked inverse and product), so an uncapped chunk·b²
    OOMs HBM at large blocks. Capping chunk·b² at 128M f32 elements
    (512 MB per temp) keeps the factor transient under 2 GiB: b=8192 gets
    chunk 2 (128M // 8192² = 2) and b=4096 chunk 8, whose factor
    programs hold 1.76 and 1.57 GiB of temps by the deviceless v5e compile
    (2.79 and 2.60 before the inverse was blocked: PERF.md section 6,
    PR 29); b≤2896 keeps the full batch of 16."""
    if jax.default_backend() == "cpu":
        return 1
    chunk = 16
    if block_size:
        chunk = min(chunk, max(1, (128 << 20) // (block_size * block_size)))
    return chunk


def block_coordinate_descent(
    A: RowMatrix,
    B: RowMatrix,
    block_size: int,
    num_iters: int,
    lam: float = 0.0,
    row_weights: Optional[jax.Array] = None,
    checkpoint_dir: Optional[str] = None,
    cache_grams: Optional[bool] = None,
) -> Tuple[List[jax.Array], List[Tuple[int, int]]]:
    """Solve min_W ||A W - B||² + lam ||W||² block-by-block.

    Returns (per-block weight matrices, block column ranges). The caller
    (BlockLinearMapper) keeps the blocks — applying block-by-block is how
    the reference streams 256k-dim models through memory. Every shape runs
    the one body (``_solve_fused``): a ``block_size`` of d or more is one
    block d wide, and a ragged last block comes back at its true width.

    With ``checkpoint_dir``, solver state (W blocks + residual) is written
    after every epoch via orbax and the solve resumes from the latest epoch
    on restart — the fault-recovery analog of Spark's lineage recompute
    (SURVEY.md §5 failure-detection row): deterministic re-execution from
    the last epoch boundary instead of RDD lineage.

    ``cache_grams`` (default: auto) precomputes each block's gram ridge
    INVERSE once — grams are epoch-invariant, so multi-epoch solves drop
    the dominant 2·n·b² FLOPs from every epoch after the first, and the
    per-epoch solve is a pure MXU gemm (TPU triangular solves are
    sequential and would dominate otherwise). Auto enables it when
    num_iters > 1 and the (num_blocks · b²) factors fit a quarter of the
    HBM budget.
    """
    A._check_aligned(B)
    mesh, axis = A.mesh, config.data_axis
    d = A.data.shape[1]
    k = B.data.shape[1]
    # A may be stored bf16 (throughput mode); solver state — weights,
    # residual, lam, grams — always lives in the accumulation dtype.
    dtype = A.data.dtype
    cdtype = jnp.dtype(config.accum_dtype)
    blocks = [(s, min(s + block_size, d)) for s in range(0, d, block_size)]

    weighted = row_weights is not None
    if weighted:
        w_rows = jnp.asarray(row_weights, dtype=dtype)
        if w_rows.shape[0] != A.padded_rows:
            w_rows = jnp.pad(w_rows, (0, A.padded_rows - w_rows.shape[0]))
        w_rows = jax.device_put(
            w_rows, jax.sharding.NamedSharding(mesh, P(axis))
        )
    else:
        w_rows = jnp.zeros((A.padded_rows,), dtype=dtype)
        w_rows = jax.device_put(
            w_rows, jax.sharding.NamedSharding(mesh, P(axis))
        )

    if cache_grams is None:
        itemsize = jnp.dtype(cdtype).itemsize
        width = blocks[0][1] - blocks[0][0]  # a ragged last block is padded
        factor_bytes = len(blocks) * width * width * itemsize
        from keystone_tpu.utils.metrics import device_hbm_bytes

        cache_grams = num_iters > 1 and factor_bytes < device_hbm_bytes() // 4
    lam_arr = jnp.asarray(lam, dtype=cdtype)

    W = [jnp.zeros((e - s, k), dtype=cdtype) for s, e in blocks]
    # jnp.array COPIES: astype is a no-op alias when dtypes already
    # match, and the first update DONATES R — donating an alias of the
    # caller's B.data would delete their labels out from under them.
    R = jnp.array(B.data, dtype=cdtype)
    sharding = jax.sharding.NamedSharding(mesh, P(axis))
    fingerprint = None
    if checkpoint_dir is not None:
        fingerprint = _make_fingerprint(
            B, d, block_size, lam, weighted,
            a_probe=float(jnp.sum(A.data[0]) + jnp.sum(A.data[A.n - 1])),
            a_dtype=dtype,
        )
    start_epoch, W, R = _resume_or_default(
        checkpoint_dir, fingerprint, W, R, sharding
    )
    if start_epoch >= num_iters:
        return W, blocks
    return _solve_fused(
        A, blocks, lam_arr, w_rows, W, R, num_iters, start_epoch,
        cache_grams, weighted, checkpoint_dir, fingerprint, mesh, axis,
    )


def _solve_fused(
    A, blocks, lam_arr, w_rows, W, R, num_iters, start_epoch, cache_grams,
    weighted, checkpoint_dir, fingerprint, mesh, axis,
):
    """The solve body for a feature matrix in HBM: one stacked copy of A's
    column blocks → with ``cache_grams`` the blocks' ridge inverses, a chunk
    of blocks a program → one epochs program (one an epoch under
    ``checkpoint_dir``). Three programs where the blocks tile d. A single
    block is as wide as d. A ragged last block is padded with zero columns
    to the blocks' width inside the stacking program, with 1 for λ on the
    pad's diagonal (``_pad_ridge``); W is trimmed to the true widths before
    it is saved or returned, so checkpoints and the result never see the pad."""
    from keystone_tpu.utils.metrics import active_tracer, span_of

    # The three spans time the host: tracing and dispatching each phase.
    # The device runs a phase after its span has closed, so in a profiler
    # trace each names the gap in front of its program.
    tracer = active_tracer()  # resolved once per solve
    precision = _precision()
    fold = fold_blocks(mesh.shape[axis])
    nb = len(blocks)
    b = blocks[0][1] - blocks[0][0]
    pad = nb * b - blocks[-1][1]

    def true_widths(W3):
        W = [W3[i] for i in range(nb)]
        if pad:
            W[-1] = W[-1][: b - pad]
        return W

    with span_of(tracer, "solver.stack", "solver", blocks=nb):
        a3 = _stack_blocks_fn(mesh, axis, nb, pad)(A.data)
    if cache_grams:
        # Chunked: bounds the factor transient to chunk·b² buffers instead
        # of nb·b².
        chunk = _factor_chunk(b)
        count_reduced(mesh, nb * b * b * R.dtype.itemsize)  # the grams
        with span_of(tracer, "solver.factor", "solver", blocks=nb, chunk=chunk):
            if chunk >= nb:
                invs = _fused_factor_fn(
                    mesh, axis, precision, weighted, fold, pad
                )(a3, lam_arr, w_rows)
            else:
                # The CPU-emulated mesh's in-process all-reduce rendezvous
                # can deadlock when independent collective programs are in
                # flight together (7/8 threads arrive -> 40s timeout ->
                # abort): wait for each there. The TPU keeps full async
                # pipelining.
                throttle = jax.default_backend() == "cpu"
                parts = []
                for c0 in range(0, nb, chunk):
                    # Only the last chunk holds the padded block.
                    factor = _fused_factor_fn(
                        mesh, axis, precision, weighted, fold,
                        pad if c0 + chunk >= nb else 0,
                    )
                    part = factor(a3[c0 : c0 + chunk], lam_arr, w_rows)
                    if throttle:
                        part.block_until_ready()
                    parts.append(part)
                invs = jnp.concatenate(parts, axis=0)
    else:
        # Dummy scan operand: the uncached body re-derives each block's
        # inverse in-place; scan only needs a leading-nb structure to carry.
        invs = jnp.zeros((nb, 1, 1), dtype=R.dtype)
    # Aᵀ R a block visit, and without cached inverses the gram again.
    count_reduced(mesh, (num_iters - start_epoch) * nb * b * R.dtype.itemsize
                  * (R.shape[1] + (0 if cache_grams else b)))
    with span_of(tracer, "solver.epochs", "solver", blocks=nb,
                 epochs=num_iters - start_epoch):
        if pad:
            W = W[:-1] + [jnp.pad(W[-1], ((0, pad), (0, 0)))]
        W3 = jnp.stack(W)
        # One program for all the epochs, or one an epoch to save after
        # each. The cached body never forms a gram: it is the program
        # without pad.
        step = _fused_epochs_fn(
            mesh, axis, precision, weighted,
            num_iters - start_epoch if checkpoint_dir is None else 1,
            cache_grams, fold, 0 if cache_grams else pad,
        )
        if checkpoint_dir is None:
            R, W3 = step(a3, invs, R, W3, lam_arr, w_rows)
        else:
            for epoch in range(start_epoch, num_iters):
                R, W3 = step(a3, invs, R, W3, lam_arr, w_rows)
                _save_epoch(
                    checkpoint_dir, epoch + 1, true_widths(W3), R,
                    fingerprint,
                )
            wait_for_checkpoints(checkpoint_dir)
    return true_widths(W3), blocks


# -- the generated-block solve: a block operand made from the narrow input --


class BlockSource:
    """A feature matrix the solver never holds: the narrow rows ``X`` it is
    made from, and ``generator``, a pytree whose ``block(X, b)`` makes
    column block ``b`` of those rows for a traced ``b``: ``block`` columns,
    [b·block, (b+1)·block) of a matrix ``dim`` wide, zeros past ``dim`` in
    a ragged last block. It has to be a pure function of each row alone:
    the solver calls it on a shard's rows, the generator's arrays
    replicated. ``BlockLeastSquaresEstimator.fit`` takes the generated path
    for an operand of this type; an array takes the stored one."""

    def __init__(self, X, generator, block: int, dim: int):
        self.X = X
        self.generator = generator
        self.block = int(block)
        self.dim = int(dim)


@lru_cache(maxsize=None)
def _making_bytes_of(tree, leaves, rows: int, cols: int, dtype) -> int:
    aval = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    generator = jax.tree_util.tree_unflatten(tree, [aval(*a) for a in leaves])
    memory = jax.jit(lambda g, x, b: g.block(x, b)).lower(
        generator, aval((rows, cols), dtype), aval((), jnp.int32)).compile().memory_analysis()
    if memory is None:
        return 0
    return int(memory.argument_size_in_bytes + memory.temp_size_in_bytes
               + memory.output_size_in_bytes)


def _making_bytes(generator, rows: int, cols: int, dtype) -> int:
    """What making one block of ``rows`` rows holds beside the solver's
    working blocks: ``generator.block`` compiled alone for this backend,
    its temporaries, the block it makes (masked and centred into another),
    and its arguments, for the copy of the rows in the layout the block is
    made from (the described v5e's compile of the whole solve holds one);
    once a process and a shape."""
    leaves, tree = jax.tree_util.tree_flatten(generator)
    return _making_bytes_of(tree, tuple((np.shape(a), jnp.result_type(a)) for a in leaves),
                            rows, cols, jnp.dtype(dtype))


def _remake(generator, xl, b):
    """A later epoch's block that was not kept, made again as the first
    epoch made it."""
    return generator.block(xl, b)


def _fetch(store, b):
    """A later epoch's kept block: its slot of the store."""
    return lax.dynamic_index_in_dim(store, b, keepdims=False)


@lru_cache(maxsize=None)
def _generated_solve_fn(mesh: Mesh, axis: str, precision, fold: int, block: int,
                        num_blocks: int, num_epochs: int, keep: int, pad: int,
                        centre: bool):
    """The whole generated-block solve as ONE program, per shard under
    shard_map, like ``kernel_ridge._block_solve_fn``. The first epoch's scan
    makes each block from the shard's rows (``generator.block``), centres
    its columns over the logical rows (the means reduced like a gram, and
    kept), forms its gram's ridge inverse, updates, and writes the block to
    slot ``b`` of a store of ``keep`` slots where ``b < keep``. The later
    epochs' scan reads a kept block from its slot and makes the others
    again, centred by the kept means: the same arithmetic on the same
    values, so a block made again is the block the first epoch made, bit
    for bit. The store is an argument the program writes in place and
    hands back (donated on a chip): an array of the process for the
    length of the solve, not scratch the runtime keeps reserved for as
    long as the program stays loaded. The cached inverses stay in the
    program. Pad rows are masked out of every block; a ragged last block's
    pad columns are zero, with 1 on their ridge diagonal (``_pad_ridge``)."""
    width = mesh.shape[axis]

    def local(xl, generator, yl, lam, n, store):
        rows = xl.shape[0]
        live = (lax.axis_index(axis) * rows + jnp.arange(rows) < n)[:, None]
        ridge = _pad_ridge(lam, num_blocks, block, pad)

        def centred(a, mean):
            with device_scope("solver.generate"):
                return jnp.where(live, a - mean, 0.0) if centre else a

        def make(b):
            with device_scope("solver.generate"):
                return jnp.where(live, generator.block(xl, b), 0.0)

        def first(carry, xs):
            r, store = carry
            b, w_b, ridge_b = xs
            a = make(b)
            mean = jnp.zeros((1, block), a.dtype)
            if centre:
                mean = sharded_rowsum(
                    lambda v: v.sum(axis=0, keepdims=True), axis, width, (a,),
                    scope="coll.moments",
                ) / n.astype(a.dtype)
            a = centred(a, mean)
            if keep:
                with device_scope("solver.generate"):
                    store = keep_block(store, a, b, keep, num_blocks)
            inv = _local_gram_inv(a, a, ridge_b, precision, axis, width)
            r, w_b = _local_solve_update(a, a, inv, r, w_b, precision, axis, width)
            return (r, store), (w_b, inv, mean)

        def fetched(b, _mean):
            with device_scope("solver.fetch"):
                return _fetch(store, b)

        def remade(b, mean):
            with device_scope("solver.generate"):
                a = jnp.where(live, _remake(generator, xl, b), 0.0)
            return centred(a, mean)

        def later(r, xs):
            b, w_b, inv, mean = xs
            a = read_or_make(b < keep, keep, num_blocks, fetched, remade, b, mean)
            return _local_solve_update(a, a, inv, r, w_b, precision, axis, width)

        def epoch(carry, _):
            r, w3 = carry
            r, w3 = lax.scan(later, r, (order, w3, invs, means))
            return (r, w3), None

        order = jnp.arange(num_blocks)
        w3 = jnp.zeros((num_blocks, block, yl.shape[1]), yl.dtype)
        # The scans' own slices and stacked results belong to the update;
        # the generation, gram, Cholesky and inverse are scopes inside.
        with device_scope("solver.update"):
            (r, store), (w3, invs, means) = lax.scan(
                first, (yl, store), (order, w3, ridge))
            (r, w3), _ = lax.scan(epoch, (r, w3), None, length=num_epochs - 1)
        return w3, means, store

    sm = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(), P(), P(None, axis)),
        out_specs=(P(), P(), P(None, axis)),
        check_vma=False,
    )
    return jax.jit(sm, donate_argnums=_donate(mesh, 5))


def block_coordinate_descent_generated(
    source: BlockSource, B: RowMatrix, num_iters: int, lam: float = 0.0,
    centre: bool = True,
) -> Tuple[List[jax.Array], List[Tuple[int, int]], Optional[jax.Array]]:
    """``block_coordinate_descent`` on a feature matrix that is never
    held: each column block is made from ``source.X`` when a visit needs
    it (``BlockSource``). Which of the first epoch's blocks stay in HBM for
    the later epochs, and which are made again, is
    ``row_matrix._blocks_kept``'s answer from sizes the code can see, with
    the cached inverses and what making a block holds (``_making_bytes``)
    counted beside the store. ``centre`` subtracts each
    column's mean over the rows as its block is made (the estimator's
    intercept). Returns (weight blocks at their true widths, block column
    ranges, the columns' means (dim,), or None without ``centre``)."""
    from keystone_tpu.utils.metrics import active_tracer, device_hbm_bytes, span_of

    A = RowMatrix.from_array(source.X)
    A._check_aligned(B)
    mesh, axis = A.mesh, config.data_axis
    block, dim = source.block, source.dim
    nb = -(-dim // block)
    pad = nb * block - dim
    k = B.data.shape[1]
    cdtype = jnp.dtype(config.accum_dtype)
    itemsize = cdtype.itemsize
    rows = A.padded_rows // mesh.shape[axis]
    keep = _blocks_kept(
        rows, block, nb, num_iters, rows * (A.data.shape[1] + k) * itemsize,
        itemsize, device_hbm_bytes(), factor_bytes=nb * block * block * itemsize,
        making_bytes=_making_bytes(source.generator, rows, A.data.shape[1], A.data.dtype))
    # The grams, the means and Aᵀ R of every visit cross the mesh.
    count_reduced(mesh, nb * block * itemsize * (block + int(centre))
                  + num_iters * nb * block * k * itemsize)
    # What the solve makes: every block once, and again in each later epoch
    # where it has no slot. ``branches`` where the generator is a stack of
    # branches (``workflow.pipeline.BranchStack``).
    with span_of(active_tracer(), "bcd.generated", "solver",
                 generated_blocks=nb + (num_iters - 1) * (nb - keep),
                 kept_blocks=keep, kept_bytes=keep * A.padded_rows * block * itemsize,
                 branches=int(getattr(source.generator, "branches", 0)),
                 block=block, epochs=num_iters):
        solve = _generated_solve_fn(
            mesh, axis, _precision(), fold_blocks(mesh.shape[axis]), block, nb,
            num_iters, keep, pad, bool(centre))
        store = jnp.zeros((keep, A.padded_rows, block), A.data.dtype,
                          device=jax.sharding.NamedSharding(mesh, P(None, axis)))
        W3, means, _ = solve(A.data, source.generator, B.data.astype(cdtype),
                             jnp.asarray(lam, cdtype), jnp.asarray(A.n, jnp.int32),
                             store)
    blocks = [(s, min(s + block, dim)) for s in range(0, dim, block)]
    W = [W3[i][: e - s] for i, (s, e) in enumerate(blocks)]
    return W, blocks, means.reshape(-1)[:dim] if centre else None


def _make_fingerprint(
    B: RowMatrix,
    d: int,
    block_size: int,
    lam,
    weighted: bool,
    a_probe: float,
    a_dtype,
) -> dict:
    """Problem identity for checkpoint binding. Probes use LOGICAL rows
    (first and last real row), so the device-resident and host-streamed
    paths produce identical fingerprints and can resume each other. The
    storage dtype is part of the identity — an f32 solve must not resume a
    bf16 one (mixed-precision epochs with no warning). ``device_count`` /
    ``data_axis`` are the per-shard manifest: same problem on a different
    mesh width either MIGRATES at restore (``utils.mesh.reshard_state``
    trims and re-pads the residual onto the new shard multiple — elastic
    mesh, default on, counted) or refuses typed (``MeshMismatchError``)
    with ``KEYSTONE_ELASTIC_MESH=0`` — never resumed into
    differently-folded accumulators, never silently discarded."""
    from keystone_tpu.utils.mesh import num_data_shards

    return {
        "rows": B.padded_rows,
        "n": B.n,
        "d": d,
        "k": B.data.shape[1],
        "block_size": block_size,
        "lam": float(lam),
        "weighted": weighted,
        "a_dtype": str(jnp.dtype(a_dtype)),
        "a_probe": a_probe,
        "b_probe": float(jnp.sum(B.data[0]) + jnp.sum(B.data[B.n - 1])),
        "device_count": int(num_data_shards(B.mesh)),
        "data_axis": str(config.data_axis),
    }


def _resume_or_default(checkpoint_dir, fingerprint, W, R, sharding):
    """Restore (epoch, W, R) from a matching checkpoint, else the defaults."""
    if checkpoint_dir is None:
        return 0, W, R
    restored = _restore_latest(checkpoint_dir, fingerprint)
    if restored is None:
        return 0, W, R
    epoch, W_np, R_np = restored
    W = [jnp.asarray(w) for w in W_np]
    R = jax.device_put(jnp.asarray(R_np), sharding)
    return epoch, W, R


# One async checkpointer per checkpoint directory (keyed by abspath):
# writes overlap the next epoch's device work; orbax's save() itself blocks
# on any previous in-flight save, so at most one write per directory is ever
# outstanding. Per-directory scoping confines a failed background write to
# the solve that issued it, and wait_for_checkpoints closes + drops the
# entry at every solver return so instances don't accumulate
# (SURVEY.md §5 failure-recovery row).
_ASYNC_CKPT: dict = {}


def _async_checkpointer(ckpt_dir: str):
    import os

    import orbax.checkpoint as ocp

    key = os.path.abspath(ckpt_dir)
    cp = _ASYNC_CKPT.get(key)
    if cp is None:
        cp = ocp.AsyncCheckpointer(ocp.PyTreeCheckpointHandler())
        _ASYNC_CKPT[key] = cp
    return cp


def _save_epoch(ckpt_dir: str, epoch: int, W, R, fingerprint) -> None:
    import os

    path = os.path.join(os.path.abspath(ckpt_dir), f"epoch_{epoch}")
    # Host-resident pytree: checkpoints cross process/mesh boundaries, so
    # shardings are re-applied on restore rather than persisted. The D2H
    # fetch is synchronous; serialization + write run in the background
    # (save blocks internally on the previous in-flight save).
    tree = {
        "epoch": epoch,
        "W": [np.asarray(w) for w in W],
        "R": np.asarray(R),
        "fingerprint": dict(fingerprint),
    }
    _async_checkpointer(ckpt_dir).save(path, tree, force=True)
    # JSON mesh sidecar: the static lint's (KG107) no-execution window
    # into what mesh this directory's epochs were folded under.
    from keystone_tpu.utils.mesh import write_mesh_manifest

    write_mesh_manifest(ckpt_dir, fingerprint)


def wait_for_checkpoints(ckpt_dir: str) -> None:
    """Block until ``ckpt_dir``'s in-flight epoch save is durable, then
    release its checkpointer. The solvers call this before returning;
    callers only need it for mid-solve probes."""
    import os

    cp = _ASYNC_CKPT.pop(os.path.abspath(ckpt_dir), None)
    if cp is not None:
        try:
            cp.wait_until_finished()
        finally:
            cp.close()


def _fingerprint_matches(saved, expected) -> bool:
    if set(saved) != set(expected):
        return False
    for key, val in expected.items():
        sval = saved[key]
        if isinstance(val, float):
            if abs(float(sval) - val) > 1e-3 * max(1.0, abs(val)):
                return False
        elif sval != val:
            return False
    return True


def _restore_latest(ckpt_dir: str, fingerprint):
    import logging
    import os
    import re

    import orbax.checkpoint as ocp

    ckpt_dir = os.path.abspath(ckpt_dir)
    if not os.path.isdir(ckpt_dir):
        return None
    epochs = []
    for name in os.listdir(ckpt_dir):
        m = re.fullmatch(r"epoch_(\d+)", name)
        if m:
            epochs.append(int(m.group(1)))
    if not epochs:
        return None
    latest = max(epochs)
    tree = ocp.PyTreeCheckpointer().restore(
        os.path.join(ckpt_dir, f"epoch_{latest}")
    )
    from keystone_tpu.utils.mesh import mesh_resume_decision, reshard_state

    # Pre-manifest snapshots (no device_count/data_axis keys) compare
    # with the absent keys backfilled as wildcards (the shared
    # mesh_resume_decision triage), so a legacy epoch checkpoint of the
    # SAME problem still resumes after the manifest upgrade instead of
    # silently restarting at epoch 0. Same problem on a different mesh
    # width migrates (elastic, counted) or refuses typed.
    decision, saved_fp = mesh_resume_decision(
        tree.get("fingerprint"), fingerprint,
        f"BCD checkpoint {ckpt_dir}",
        extra_mesh_keys=("rows",), same_problem=_fingerprint_matches,
    )
    if decision == "fresh":
        logging.getLogger("keystone_tpu").warning(
            "checkpoint dir %s holds a different solve (fingerprint "
            "mismatch); starting fresh",
            ckpt_dir,
        )
        return None
    if decision == "migrate":
        tree = reshard_state(
            dict(tree, fingerprint=saved_fp), family="bcd_epoch"
        )
    return int(tree["epoch"]), tree["W"], tree["R"]


def _refuse_bcd_mesh_mismatch(saved_fp, expected_fp, ckpt_dir) -> bool:
    """The shared mesh-width rule (``utils.mesh.refuse_mesh_mismatch``)
    with the BCD-specific exclusions: padded ``rows`` follow the mesh (the
    shard multiple changes them for the same logical solve), and problem
    identity uses the solver's tolerant float matching. Returns True when
    the elastic path should migrate the checkpoint via ``reshard_state``;
    raises the typed ``MeshMismatchError`` when elastic migration is
    pinned off (resuming W/R folded under one shard layout into another
    unmigrated would be a wrong-answer resume); other mismatches stay on
    the warn-and-start-fresh path."""
    from keystone_tpu.utils.mesh import refuse_mesh_mismatch

    return refuse_mesh_mismatch(
        saved_fp, expected_fp, f"BCD checkpoint {ckpt_dir}",
        extra_mesh_keys=("rows",), same_problem=_fingerprint_matches,
    )


def _reshard_bcd_R(state, layout, where):
    """Shared residual migration for both BCD checkpoint families: trim
    the zero pad rows folded under the OLD shard multiple off ``R``,
    re-pad to the NEW multiple, and rewrite the fingerprint's ``rows`` +
    mesh keys. Pad rows are zero by construction (A and B are zero-padded,
    so every epoch's residual update leaves them zero) — a nonzero pad
    region can only mean a torn per-shard payload, which refuses typed."""
    from keystone_tpu.utils.mesh import (
        pad_multiple,
        pad_rows,
        reshard_refused,
    )

    fp = dict(state.get("fingerprint") or {})
    R = state.get("R")
    n, rows = int(fp.get("n", -1)), int(fp.get("rows", -1))
    R = np.asarray(R) if R is not None else None
    if R is None or n < 0 or R.shape[0] != rows or n > rows:
        raise reshard_refused(
            where,
            "residual shape does not match its fingerprint "
            "(torn or partially written checkpoint)",
        )
    if R[n:].any():
        raise reshard_refused(
            where,
            "nonzero rows in the residual's pad region — a partial "
            "per-shard write, not a clean epoch snapshot",
        )
    R_new, _ = pad_rows(R[:n], pad_multiple(layout.num_shards))
    fp["rows"] = int(R_new.shape[0])
    fp["device_count"] = int(layout.num_shards)
    fp["data_axis"] = str(layout.axis)
    return dict(state, R=R_new, fingerprint=fp)


def _reshard_bcd_epoch(state, layout):
    """Elastic-mesh adapter for epoch checkpoints (orbax ``epoch_N``
    trees): W blocks are replicated (placement-free) and pass through
    byte-identical; only the residual's row padding follows the mesh."""
    return _reshard_bcd_R(state, layout, "BCD epoch checkpoint")


def _reshard_bcd_stream(state, layout):
    """Elastic-mesh adapter for mid-epoch block snapshots: W blocks and
    the cached ridge inverses are replicated (placement-free); the
    residual re-pads exactly as the epoch family does."""
    state = _reshard_bcd_R(state, layout, "BCD block checkpoint")
    if int(state.get("block", -1)) < 0 or int(state.get("epoch", -1)) < 0:
        from keystone_tpu.utils.mesh import reshard_refused

        raise reshard_refused(
            "BCD block checkpoint",
            "snapshot is missing its block cursor",
        )
    return state


register_reshard_adapter("bcd_epoch", _reshard_bcd_epoch)
register_reshard_adapter("bcd_stream", _reshard_bcd_stream)


def assemble_blocks(W: List[jax.Array]) -> jax.Array:
    """Concatenate per-block solutions into the full (d, k) matrix (blocks
    are contiguous ascending column ranges by construction)."""
    return jnp.concatenate(W, axis=0)


_BCD_CKPT_KEY = "bcd_stream"


def _bcd_ckpt_store(checkpoint_dir: str):
    from keystone_tpu.workflow.disk_cache import DiskCache

    return DiskCache(checkpoint_dir, suffix=".ckpt.pkl")


def _bcd_ckpt_save(store, fingerprint, epoch, block, W, R, invs) -> None:
    """Mid-epoch snapshot: solver state (W blocks + residual + the ridge
    inverses computed so far) and the block cursor — ``block`` blocks of
    ``epoch`` are complete. The atomic DiskCache rewrite means a kill
    mid-save leaves the previous complete snapshot. D2H of R is the sync
    this costs, once per K blocks."""
    from keystone_tpu.utils.metrics import reliability_counters

    store.put(
        _BCD_CKPT_KEY,
        {
            "fingerprint": dict(fingerprint),
            "epoch": int(epoch),
            "block": int(block),
            "W": [np.asarray(w) for w in W],
            "R": np.asarray(R),
            "invs": {
                i: np.asarray(v) for i, v in enumerate(invs) if v is not None
            },
        },
        overwrite=True,
    )
    from keystone_tpu.utils.mesh import write_mesh_manifest

    write_mesh_manifest(store.root, fingerprint)
    reliability_counters.bump("checkpoints_written")


def _bcd_ckpt_resume(store, fingerprint):
    """The block snapshot, or None when absent / bound to another solve.
    Same mesh triage as the epoch family: a snapshot of THIS solve under
    a different mesh width migrates (elastic, counted) or refuses typed —
    it is never silently discarded as if it were another problem."""
    import logging

    from keystone_tpu.utils.mesh import mesh_resume_decision, reshard_state

    state = store.get(_BCD_CKPT_KEY)
    if state is None:
        return None
    decision, saved_fp = mesh_resume_decision(
        state.get("fingerprint"), fingerprint,
        f"BCD block checkpoint {store.root}",
        extra_mesh_keys=("rows",), same_problem=_fingerprint_matches,
    )
    if decision == "fresh":
        logging.getLogger("keystone_tpu").warning(
            "block checkpoint in %s holds a different solve (fingerprint "
            "mismatch); ignoring it", store.root,
        )
        return None
    if decision == "migrate":
        state = reshard_state(
            dict(state, fingerprint=saved_fp), family="bcd_stream"
        )
    return state


def block_coordinate_descent_streamed(
    A_host,
    B: RowMatrix,
    block_size: int,
    num_iters: int,
    lam: float = 0.0,
    row_weights: Optional[jax.Array] = None,
    checkpoint_dir: Optional[str] = None,
    col_center: Optional[np.ndarray] = None,
    checkpoint_every: Optional[int] = None,
) -> Tuple[List[jax.Array], List[Tuple[int, int]]]:
    """BCD for feature matrices that exceed HBM: A stays in host RAM and
    column blocks stream to the device double-buffered — the transfer of
    block b+1 overlaps the MXU work on block b (SURVEY.md §7 hard part 1:
    the replacement for Spark's cached-RDD block access).

    ``A_host`` is a dense ndarray or a CSR ``SparseBatch`` (the large-vocab
    text path): sparse blocks densify per column block right here, so an
    (n, vocab) dense matrix never exists anywhere.

    ``col_center`` (dense only): per-column means subtracted from each
    block AS it streams — the intercept-centering of the estimator layer
    without a second full-size host copy of A (each block is a fresh copy
    on its way to the device anyway).

    The first epoch fuses gram+Cholesky into each block update and keeps
    the small (b, b) factors resident, so later epochs run the cheap
    cached update while still streaming only one block of A at a time.

    Reliability: each block's H2D retries transient RESOURCE_EXHAUSTED
    with backoff (a column block can't be split without changing the
    solve — persistent OOM propagates with the advice to shrink
    ``block_size``). With ``checkpoint_dir``, epoch snapshots (orbax, as
    before) are supplemented by mid-epoch block snapshots every
    ``checkpoint_every`` blocks (default ``config.checkpoint_every``,
    env ``KEYSTONE_CHECKPOINT_EVERY``; 0 = epoch-only) holding W, R, the
    ridge inverses computed so far, and the block cursor — a killed fit
    resumes recomputing at most K block updates.
    """
    from keystone_tpu.utils.metrics import active_tracer
    from keystone_tpu.utils.reliability import RetryPolicy, active_plan
    from keystone_tpu.utils.sparse import SparseBatch

    tracer = active_tracer()  # resolved once per solve, like the plan
    sparse = isinstance(A_host, SparseBatch)
    if sparse and col_center is not None:
        raise ValueError(
            "col_center is a dense-path feature (sparse fits learn the "
            "intercept via an appended ones column)"
        )
    mesh, axis = B.mesh, config.data_axis
    if A_host.shape[0] != B.n:
        raise ValueError(
            f"A rows ({A_host.shape[0]}) must match B rows ({B.n})"
        )
    d = A_host.shape[1]
    k = B.data.shape[1]
    # Streamed blocks take the storage dtype (bf16 halves H2D traffic in
    # throughput mode); solver state stays in the accumulation dtype.
    dtype = storage_dtype()
    cdtype = jnp.dtype(config.accum_dtype)
    blocks = [(s, min(s + block_size, d)) for s in range(0, d, block_size)]
    nb = len(blocks)
    pad = B.padded_rows - A_host.shape[0]
    sharding = jax.sharding.NamedSharding(mesh, P(axis))

    # Center in A's own (full-width) dtype BEFORE any storage-dtype cast:
    # subtracting a large mean after bf16 quantization would leave the
    # centered values carrying the uncentered magnitude's rounding error
    # (catastrophic cancellation) — the device path centers in f32 too.
    center = (
        None if col_center is None else np.asarray(col_center, dtype=A_host.dtype)
    )

    def host_block(i: int) -> np.ndarray:
        """Host-side block prep — slice/densify, center, cast, pad. Pure
        numpy on read-only A_host, so the prefetch thread runs it safely."""
        s, e = blocks[i]
        if sparse:
            block = A_host.densify(s, e, dtype=dtype)
        elif center is not None:
            block = np.asarray(A_host[:, s:e] - center[s:e], dtype=dtype)
        else:
            block = np.ascontiguousarray(A_host[:, s:e], dtype=dtype)
        if pad:
            block = np.pad(block, ((0, pad), (0, 0)))
        return block

    plan = active_plan()
    retry = RetryPolicy()

    def _transfer(block: np.ndarray) -> jax.Array:
        def attempt():
            if plan is not None:
                plan.maybe_raise("oom")
            return jax.device_put(block, sharding)

        try:
            return retry.call(attempt, site="h2d", counter="h2d_retries")
        except Exception as exc:
            from keystone_tpu.utils.reliability import is_oom

            if is_oom(exc):
                raise type(exc)(
                    f"{exc} [streamed BCD: a ({block.shape[0]}, "
                    f"{block.shape[1]}) block does not fit on device even "
                    "after retries; reduce block_size]"
                ) from exc
            raise

    def put_host(block: np.ndarray) -> jax.Array:
        """H2D one prepared block, retrying transient RESOURCE_EXHAUSTED
        (real or the harness's ``oom`` site). Unlike the row-chunked
        solver there is no downshift — halving a column block would
        change the solve — so a persistent OOM propagates, annotated.
        Spanned per block when tracing is live."""
        if tracer is None:
            return _transfer(block)
        t0 = tracer.now()
        out = _transfer(block)
        tracer.record(
            "solver.h2d", "solver", t0,
            shape=[int(block.shape[0]), int(block.shape[1])],
        )
        return out

    def put(i: int) -> jax.Array:
        return put_host(host_block(i))

    weighted = row_weights is not None
    if weighted:
        w_rows = jnp.asarray(row_weights, dtype=dtype)
        if w_rows.shape[0] != B.padded_rows:
            w_rows = jnp.pad(w_rows, (0, B.padded_rows - w_rows.shape[0]))
    else:
        w_rows = jnp.zeros((B.padded_rows,), dtype=dtype)
    w_rows = jax.device_put(w_rows, sharding)

    first = _first_epoch_update_fn(
        mesh, axis, _precision(), weighted, fold_blocks(mesh.shape[axis])
    )
    cached = _cached_block_update_fn(
        mesh, axis, _precision(), weighted, fold_blocks(mesh.shape[axis])
    )
    lam_arr = jnp.asarray(lam, dtype=cdtype)
    throttle = jax.default_backend() == "cpu"

    W = [jnp.zeros((e - s, k), dtype=cdtype) for s, e in blocks]
    invs: List[Optional[jax.Array]] = [None] * nb
    # jnp.array COPIES: astype is a no-op alias when dtypes already
    # match, and the first update DONATES R — donating an alias of the
    # caller's B.data would delete their labels out from under them.
    R = jnp.array(B.data, dtype=cdtype)
    fingerprint = None
    if checkpoint_dir is not None:
        if sparse:
            a_probe = A_host.row_sum(0) + A_host.row_sum(len(A_host) - 1)
        else:
            # Probe the EFFECTIVE (centered) matrix so device-path and
            # streamed-path checkpoints stay mutually resumable.
            shift = 2.0 * float(center.sum()) if center is not None else 0.0
            a_probe = float(A_host[0].sum() + A_host[-1].sum()) - shift
        fingerprint = _make_fingerprint(
            B, d, block_size, lam, weighted, a_probe=a_probe, a_dtype=dtype
        )
    # On resume, ridge inverses rebuild lazily: the `first` update at the
    # resumed epoch recomputes them as part of a normal update.
    start_epoch, W, R = _resume_or_default(
        checkpoint_dir, fingerprint, W, R, sharding
    )
    # Mid-epoch block snapshots (atomic DiskCache) can be FURTHER along
    # than the last orbax epoch save; prefer whichever resumes later.
    start_block = 0
    every = (
        config.checkpoint_every if checkpoint_every is None
        else int(checkpoint_every)
    )
    ckpt_store = None
    if checkpoint_dir is not None and every > 0:
        from keystone_tpu.utils.metrics import reliability_counters

        ckpt_store = _bcd_ckpt_store(checkpoint_dir)
        state = _bcd_ckpt_resume(ckpt_store, fingerprint)
        if state is not None and (state["epoch"], state["block"]) > (
            start_epoch, 0,
        ):
            start_epoch, start_block = state["epoch"], state["block"]
            W = [jnp.asarray(w) for w in state["W"]]
            R = jax.device_put(jnp.asarray(state["R"]), sharding)
            for i, v in state["invs"].items():
                invs[int(i)] = jnp.asarray(v)
            reliability_counters.bump("checkpoints_resumed")
            if start_block >= nb:  # snapshot landed on an epoch boundary
                start_epoch, start_block = start_epoch + 1, 0
    if start_epoch >= num_iters:
        if ckpt_store is not None:
            ckpt_store.delete(_BCD_CKPT_KEY)  # consumed by this solve
        return W, blocks
    # KEYSTONE_STREAM_NO_OVERLAP=1 serializes transfer and compute — it
    # exists so a bench can MEASURE what double-buffering buys; it is
    # never the right setting for real runs.
    from keystone_tpu.config import env_flag
    from keystone_tpu.loaders.stream import PrefetchIterator

    no_overlap = env_flag("KEYSTONE_STREAM_NO_OVERLAP")
    # Host-side block prep (densify/center/cast/pad) runs on a background
    # prefetch thread, config.prefetch_depth blocks ahead, on top of the
    # existing H2D double buffer: the device then never waits on the numpy
    # prep either. depth=0 keeps the prep inline on the consumer thread.
    depth = 0 if no_overlap else max(0, int(config.prefetch_depth))
    total = (num_iters - start_epoch) * nb - start_block
    src = None
    if depth > 0:

        def host_blocks():
            for e in range(start_epoch, num_iters):
                for i in range(start_block if e == start_epoch else 0, nb):
                    yield host_block(i)

        src = PrefetchIterator(host_blocks(), depth)

    def put_ahead(i_next: int) -> jax.Array:
        if src is not None:
            return put_host(next(src))
        return put(i_next)

    from keystone_tpu.utils.flight_recorder import ProgressReporter

    # Always-on solve journey: block/epoch progress with a known total
    # (so ETA is live), checkpoint age, stall watchdog; a death mid-epoch
    # force-dumps the solver recorder naming the last completed block.
    progress = ProgressReporter("bcd_streamed", total_units=total)
    n_rows = int(A_host.shape[0])
    try:
        with progress:
            next_buf = None if no_overlap else put_ahead(start_block)
            consumed = 0
            blocks_done = 0
            for epoch in range(start_epoch, num_iters):
                first_block = start_block if epoch == start_epoch else 0
                for i in range(first_block, nb):
                    if no_overlap:
                        cur = put(i)
                        cur.block_until_ready()
                    else:
                        cur = next_buf
                        consumed += 1
                        # Prefetch the next block while this one computes
                        # (double buffering): H2D DMA overlaps the MXU
                        # work.
                        if consumed < total:
                            next_buf = put_ahead((i + 1) % nb)
                    was_cached = invs[i] is not None
                    t0 = tracer.now() if tracer is not None else 0
                    if invs[i] is None:
                        R, W[i], invs[i] = first(
                            cur, R, W[i], lam_arr, w_rows
                        )
                    else:
                        R, W[i] = cached(cur, invs[i], R, W[i], w_rows)
                    if throttle:
                        R.block_until_ready()
                    if tracer is not None:
                        # Dispatch time unless throttled (the block above
                        # makes the CPU path synchronous anyway).
                        tracer.record(
                            "solver.block_update", "solver", t0, epoch=epoch,
                            block=i, cached_inverse=was_cached,
                            async_dispatch=not throttle,
                        )
                    blocks_done += 1
                    progress.unit_done(rows=n_rows, epoch=epoch, block=i)
                    if ckpt_store is not None and blocks_done % every == 0:
                        _bcd_ckpt_save(
                            ckpt_store, fingerprint, epoch, i + 1, W, R,
                            invs,
                        )
                        progress.checkpoint()
                if checkpoint_dir is not None:
                    _save_epoch(checkpoint_dir, epoch + 1, W, R, fingerprint)
    finally:
        if src is not None:
            src.close()
    if checkpoint_dir is not None:
        wait_for_checkpoints(checkpoint_dir)
    if ckpt_store is not None:
        # Block snapshots are mid-flight state, consumed by the solve that
        # completes over them; the epoch-boundary orbax saves remain the
        # durable cross-run artifact (pre-existing semantics).
        ckpt_store.delete(_BCD_CKPT_KEY)
    return W, blocks
