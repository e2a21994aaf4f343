"""Ring-parallel block coordinate descent — d-axis model parallelism.

The reference has no sequence/attention machinery; SURVEY.md §5 identifies
the feature dimension (64k–256k, ≫ single-node memory) as this workload's
"long axis" and prescribes exactly this design: shard the d-axis across
the ICI mesh into per-chip feature blocks and pass residuals around a ring
— the collective-matmul / ring-attention scheduling idea applied to
blocked least squares (PAPERS.md arXiv:2112.09017 family).

Layout and schedule:

- chip c owns feature block A_c (n × d/P columns, rows replicated) and its
  weights W_c — the model axis is sharded, nothing is all-gathered;
- B's columns split into P chunks; chunk c starts on chip c as its
  residual R_c (different B columns are independent least-squares
  problems sharing A);
- each step, every chip runs one BCD block update of ITS block against the
  residual chunk it currently holds, then `ppermute`s the chunk to the
  next chip. After P steps each chunk has visited every block once (one
  full Gauss-Seidel sweep, block order rotated per chunk — an equally
  valid sweep order), and all P chips were busy every step.

Per-chip per-epoch communication is exactly n·k/P · P = n·k values over
ICI neighbor links — no psum trees, no gathers; per-chip grams are local
(columns live on one chip) and their Cholesky factors are computed once.
Compare the data-parallel path (bcd.py): that shards n and psums b×b
grams; this shards d and rings n×k/P residuals — the right trade when d
dwarfs n·k, i.e. the reference's high-dimensional featurized regime.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from keystone_tpu.config import config
from keystone_tpu.linalg.bcd import _batched_spd_inv
from keystone_tpu.linalg.row_matrix import _precision, solver_matmul, storage_dtype


@lru_cache(maxsize=None)
def _ring_solve_fn(mesh: Mesh, model_axis: str, data_axis, precision):
    """2-D-capable ring solver: columns sharded over ``model_axis`` (the
    ring); rows optionally sharded over ``data_axis`` (grams/gradients then
    psum across it — the dp×mp composition)."""
    nshards = mesh.shape[model_axis]

    def maybe_psum(x):
        return lax.psum(x, data_axis) if data_axis is not None else x

    # num_steps is a dynamic operand (fori_loop takes traced bounds, lowering
    # to while_loop), so different iteration counts share one compilation.
    def local(a_loc, b_chunk, lam, num_steps):
        # a_loc: (n_loc, d_loc) — this chip's (row shard ×) feature block
        # b_chunk: (n_loc, kc) — its shard of the chunk starting on this ring slot
        d_loc = a_loc.shape[1]
        kc = b_chunk.shape[1]
        gram = maybe_psum(solver_matmul(a_loc.T, a_loc, precision))
        # Explicit ridge inverse ONCE per chip, outside the ring loop: the
        # per-step solve becomes one MXU gemm instead of a sequential
        # triangular solve (same rework + self-correction argument as
        # bcd._local_gram_inv). The trace-scaled jitter floors cond even
        # at the lam=0.0 default — an explicit f32 inverse of a singular
        # gram would otherwise poison every ring step (the kernel_ridge
        # NOTE's divergence mode); the shift it introduces is ~1e-6
        # relative, inside solver tolerance.
        eye = jnp.eye(d_loc, dtype=gram.dtype)
        jitter = 1e-6 * (jnp.trace(gram) / d_loc)
        # Shared blocked inverse (bcd._batched_spd_inv): a trsm against
        # the full identity blows XLA:TPU's unrolled-panel temp budget at
        # large d_loc, and two thirds of it is work on zeros.
        inv = _batched_spd_inv(gram + (lam + jitter) * eye)
        idx = lax.axis_index(model_axis)
        # Solver state in the accumulation dtype even when A stores bf16.
        w0 = jnp.zeros((d_loc, nshards * kc), dtype=b_chunk.dtype)

        def step(s, carry):
            r, w = carry
            # Which chunk this ring slot holds at step s (chunks move +1/step).
            j = jnp.mod(idx - s, nshards)
            w_old = lax.dynamic_slice(w, (0, j * kc), (d_loc, kc))
            r_plus = r + solver_matmul(a_loc, w_old, precision)
            rhs = maybe_psum(solver_matmul(a_loc.T, r_plus, precision))
            w_new = solver_matmul(inv, rhs, precision)
            r_new = r_plus - solver_matmul(a_loc, w_new, precision)
            w = lax.dynamic_update_slice(w, w_new, (0, j * kc))
            r_next = lax.ppermute(
                r_new,
                model_axis,
                [(p, (p + 1) % nshards) for p in range(nshards)],
            )
            return r_next, w

        _r, w = lax.fori_loop(0, num_steps, step, (b_chunk, w0))
        return w  # (d_loc, k) — concatenates to the full W over model axis

    sm = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(data_axis, model_axis), P(data_axis, model_axis), P(), P()),
        out_specs=P(model_axis, None),
        check_vma=False,
    )
    return jax.jit(sm)


def block_coordinate_descent_ring(
    A,
    B,
    num_iters: int,
    lam: float = 0.0,
    mesh: Mesh | None = None,
) -> jax.Array:
    """Solve min_W ||A W − B||² + lam ||W||² with d-sharded ring BCD.

    A: (n, d), B: (n, k) — host or device arrays; columns of A and B are
    padded to multiples of the ring size and sharded across it. Returns
    the full (d, k) solution (model-sharded on device; slice is unpadded).

    Mesh shapes: a 1-D mesh rings over its only axis with rows replicated;
    a 2-D mesh named (data_axis, model_axis) additionally shards rows over
    the data axis and psums grams/gradients across it — data and model
    parallelism composed, the full pod-slice layout.
    """
    from keystone_tpu.utils.mesh import default_mesh

    mesh = mesh or default_mesh()
    if len(mesh.axis_names) == 1:
        axis = mesh.axis_names[0]
        data_axis = None
        row_shards = 1
    else:
        data_axis, axis = mesh.axis_names[:2]
        row_shards = mesh.shape[data_axis]
    nshards = mesh.shape[axis]
    dtype = jnp.dtype(config.default_dtype)
    A = np.asarray(A, dtype=storage_dtype())  # bf16 in throughput mode
    B = np.asarray(B, dtype=dtype)
    n, d = A.shape
    k = B.shape[1]
    pad_d = (-d) % nshards
    pad_k = (-k) % nshards
    pad_n = (-n) % row_shards
    if pad_d and lam <= 0.0:
        raise ValueError(
            f"d={d} is not a multiple of the {nshards}-chip ring; the "
            "zero-padded feature columns make the per-chip gram singular — "
            "pass lam > 0 or pad the features yourself"
        )
    if pad_d:
        A = np.pad(A, ((0, 0), (0, pad_d)))
    if pad_k:
        B = np.pad(B, ((0, 0), (0, pad_k)))
    if pad_n:
        A = np.pad(A, ((0, pad_n), (0, 0)))
        B = np.pad(B, ((0, pad_n), (0, 0)))
    A_dev = jax.device_put(A, NamedSharding(mesh, P(data_axis, axis)))
    B_dev = jax.device_put(B, NamedSharding(mesh, P(data_axis, axis)))
    solve = _ring_solve_fn(mesh, axis, data_axis, _precision())
    W = solve(
        A_dev,
        B_dev,
        jnp.asarray(lam, dtype=dtype),
        jnp.int32(num_iters * nshards),
    )
    return W[:d, :k]
