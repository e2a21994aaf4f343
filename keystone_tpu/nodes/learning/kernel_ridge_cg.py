"""Kernel ridge regression by matrix-free conjugate gradient: ``KernelRidgeCG``.

The solver ``KernelRidgeRegression`` was before it became upstream's block
Gauss-Seidel (``kernel_ridge.py``), kept under a name of its own. The
regularized system (K + λI)α = Y is solved by conjugate gradient where each
matvec computes its kernel rows on the fly inside a shard_map — every chip
holds a row shard of the training data, builds its (n_local, n) kernel
block on the MXU, multiplies, and the CG scalars reduce with psum. K is
never materialized, but a matvec holds all (n_local, n) of it at once: 10 GB
at n = 50,000 on one chip, so this is a solver for n a chip's memory takes
squared. The whole CG loop is one XLA while_loop; ``precond_landmarks``
turns on the Nyström preconditioner.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from keystone_tpu.config import config
from keystone_tpu.linalg.row_matrix import RowMatrix
from keystone_tpu.nodes.learning.kernel_ridge import KernelBlockLinearMapper
from keystone_tpu.nodes.learning.kernels import GaussianKernelGenerator, KernelGenerator
from keystone_tpu.workflow import LabelEstimator


def _kernel_matvec(mesh: Mesh, axis: str, gamma: float):
    """Row-sharded (K + λI) v with on-the-fly kernel rows and padded
    rows/cols masked out of K — the ONE operator both CG variants iterate
    on (a drift between them would silently solve different systems)."""

    from keystone_tpu.nodes.learning.kernels import pairwise_sq_dists

    def matvec(x_sharded, x_full, mask, v, lam):
        def local(xl, ml, v):
            kl = jnp.exp(-gamma * pairwise_sq_dists(xl, x_full))
            kl = kl * mask[None, :] * ml[:, None]
            return kl @ v

        out = shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P()),
            out_specs=P(axis),
            check_vma=False,
        )(x_sharded, mask, v)
        return out + lam * v

    return matvec


@lru_cache(maxsize=None)
def _cg_fn(mesh: Mesh, axis: str, gamma: float, max_iters: int, tol: float):
    """CG solve of (K_gauss + λI)α = Y with on-the-fly kernel rows."""

    matvec = _kernel_matvec(mesh, axis, gamma)

    @jax.jit
    def solve(x_sharded, x_full, mask, Y, lam):
        b = Y
        x0 = jnp.zeros_like(b)
        r0 = b  # since x0 = 0
        p0 = r0
        rs0 = jnp.sum(r0 * r0)

        def cond(carry):
            _x, _r, _p, rs, i = carry
            return (rs > tol * tol) & (i < max_iters)

        def body(carry):
            x, r, p, rs, i = carry
            Ap = matvec(x_sharded, x_full, mask, p, lam)
            alpha = rs / jnp.maximum(jnp.sum(p * Ap), 1e-30)
            x = x + alpha * p
            r = r - alpha * Ap
            rs_new = jnp.sum(r * r)
            p = r + (rs_new / jnp.maximum(rs, 1e-30)) * p
            return x, r, p, rs_new, i + 1

        x, _r, _p, rs, iters = lax.while_loop(
            cond, body, (x0, r0, p0, rs0, jnp.int32(0))
        )
        return x, rs, iters

    return solve


@lru_cache(maxsize=None)
def _pcg_fn(mesh: Mesh, axis: str, gamma: float, max_iters: int, tol: float):
    """Nyström-preconditioned CG (the Falkon-family idea, PAPERS.md):
    landmarks L give the rank-m surrogate K̂ = C W⁻¹ Cᵀ with C = k(X, L),
    W = k(L, L); Woodbury turns (K̂ + λI)⁻¹ into
        (1/λ)·(I − C (λW + CᵀC)⁻¹ Cᵀ),
    two (n, m) MXU gemms + one replicated (m, m) Cholesky solve per
    application. RBF spectra decay fast, so M⁻¹(K + λI) clusters near 1 and
    CG converges in a fraction of the iterations — same matvec, same
    stopping rule, strictly fewer steps."""

    from jax.scipy.linalg import cho_factor, cho_solve

    from keystone_tpu.nodes.learning.kernels import pairwise_sq_dists

    matvec = _kernel_matvec(mesh, axis, gamma)

    @jax.jit
    def solve(x_sharded, x_full, mask, Y, lam, L, W):
        from jax.scipy.linalg import solve_triangular

        m = W.shape[0]
        # Whitened landmark block B = C L⁻ᵀ with W = L Lᵀ: the Woodbury
        # inner matrix becomes λI + BᵀB, whose conditioning is floored by λ
        # exactly — no scale-dependent jitter games (CᵀC alone can be
        # numerically rank-deficient for wide kernels and NaN the f32
        # Cholesky). Over-regularizing only weakens the preconditioner,
        # never the solution (CG iterates on the exact operator).
        Lw = jnp.linalg.cholesky(W + 1e-5 * jnp.eye(m, dtype=W.dtype))

        def b_local(xl, ml):
            cl = jnp.exp(-gamma * pairwise_sq_dists(xl, L)) * ml[:, None]
            return solve_triangular(Lw, cl.T, lower=True).T

        B = shard_map(
            b_local,
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )(x_sharded, mask)

        def btb_local(bl):
            return lax.psum(bl.T @ bl, axis)

        BtB = shard_map(
            btb_local, mesh=mesh, in_specs=P(axis), out_specs=P(),
            check_vma=False,
        )(B)
        trace_scale = jnp.trace(BtB) / m
        G = BtB + (lam + 1e-6 * trace_scale) * jnp.eye(m, dtype=W.dtype)
        # NOTE: tried the BCD-style explicit G⁻¹ here (one-time inverse,
        # gemm per iteration) — it NaNs: the whitened Nyström G's top
        # eigenvalue is ~||B||² with only a λ floor below, cond can exceed
        # 1/eps_f32, and an explicit f32 inverse breaks PCG symmetry until
        # CG diverges. The two-pass cho_solve is the numerically safe form;
        # PCG's whole point is few iterations, so the per-iteration trsm
        # cost stays bounded.
        cholG = cho_factor(G)

        def btr(r):
            def local(bl, rl):
                return lax.psum(bl.T @ rl, axis)

            return shard_map(
                local, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(),
                check_vma=False,
            )(B, r)

        def bmul(t):
            def local(bl, t):
                return bl @ t

            return shard_map(
                local, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis),
                check_vma=False,
            )(B, t)

        def minv(r):
            return (r - bmul(cho_solve(cholG, btr(r)))) / lam

        b = Y
        x0 = jnp.zeros_like(b)
        r0 = b
        z0 = minv(r0)
        p0 = z0
        rz0 = jnp.sum(r0 * z0)
        rs0 = jnp.sum(r0 * r0)

        def cond(carry):
            _x, _r, _z, _p, _rz, rs, i = carry
            return (rs > tol * tol) & (i < max_iters)

        def body(carry):
            x, r, z, p, rz, rs, i = carry
            Ap = matvec(x_sharded, x_full, mask, p, lam)
            alpha = rz / jnp.maximum(jnp.sum(p * Ap), 1e-30)
            x = x + alpha * p
            r = r - alpha * Ap
            z = minv(r)
            rz_new = jnp.sum(r * z)
            p = z + (rz_new / jnp.maximum(rz, 1e-30)) * p
            return x, r, z, p, rz_new, jnp.sum(r * r), i + 1

        x, _r, _z, _p, _rz, rs, iters = lax.while_loop(
            cond, body, (x0, r0, z0, p0, rz0, rs0, jnp.int32(0))
        )
        return x, rs, iters

    return solve


class KernelRidgeCG(LabelEstimator):
    """Gaussian-kernel ridge regression (other kernels via the un-sharded
    fallback path of KernelBlockLinearMapper)."""

    # Fit-time diagnostic, not identity (see workflow._estimator_signature).
    _signature_exclude = ("last_cg_iters",)

    def __init__(
        self,
        kernel: KernelGenerator | None = None,
        lam: float = 1e-3,
        gamma: float | None = None,
        max_iters: int = 200,
        tol: float = 1e-5,
        predict_block_size: int = 4096,
        precond_landmarks: int | None = None,
        seed: int = 0,
    ):
        if kernel is not None and gamma is not None:
            raise ValueError("pass either `kernel` or `gamma`, not both")
        if kernel is None:
            kernel = GaussianKernelGenerator(gamma if gamma is not None else 1.0)
        self.kernel = kernel
        self.lam = lam
        self.max_iters = max_iters
        self.tol = tol
        self.predict_block_size = predict_block_size
        # Nyström preconditioning: number of landmark rows (None = plain
        # CG). ~256-1024 typically cuts RBF iteration counts several-fold.
        self.precond_landmarks = precond_landmarks
        self.seed = seed
        self.last_cg_iters: int | None = None

    def fit(self, data, labels) -> KernelBlockLinearMapper:
        X = jnp.asarray(data, dtype=config.default_dtype)
        Y = jnp.asarray(labels, dtype=config.default_dtype)
        if Y.ndim == 1:
            Y = Y[:, None]
        if not isinstance(self.kernel, GaussianKernelGenerator):
            return self._fit_dense(X, Y)
        A = RowMatrix.from_array(X)
        n_pad = A.padded_rows
        mask = jnp.zeros((n_pad,), X.dtype).at[: A.n].set(1.0)
        Y_pad = jnp.pad(Y, ((0, n_pad - Y.shape[0]), (0, 0)))
        # Replicate the kernel-column data ONCE before the CG loop; a sharded
        # x_full closed over inside matvec would re-all-gather every iteration.
        x_full = jax.device_put(
            A.data, NamedSharding(A.mesh, P())
        )
        if self.precond_landmarks and self.lam <= 0.0:
            raise ValueError(
                "precond_landmarks requires lam > 0: the Woodbury "
                "preconditioner divides by lam (plain CG handles lam=0)"
            )
        if self.precond_landmarks:
            m = min(int(self.precond_landmarks), A.n)
            rng = np.random.default_rng(self.seed)
            idx = rng.choice(A.n, size=m, replace=False)
            # On-device gather: only the m landmark rows move, never a full
            # n×d device→host round trip.
            L = jax.device_put(
                X[jnp.asarray(np.sort(idx))], NamedSharding(A.mesh, P())
            )
            W = self.kernel.block(L, L)
            solve_p = _pcg_fn(
                A.mesh,
                config.data_axis,
                float(self.kernel.gamma),
                self.max_iters,
                float(self.tol),
            )
            alpha, _rs, iters = solve_p(
                A.data, x_full, mask, Y_pad,
                jnp.asarray(self.lam, X.dtype), L, W,
            )
        else:
            solve = _cg_fn(
                A.mesh,
                config.data_axis,
                float(self.kernel.gamma),
                self.max_iters,
                float(self.tol),
            )
            alpha, _rs, iters = solve(
                A.data, x_full, mask, Y_pad, jnp.asarray(self.lam, X.dtype)
            )
        self.last_cg_iters = int(iters)
        # The model's arrays are replicated, as a linear map's are: its
        # program then sums in one order wherever the arrays lie (a saved
        # and loaded model scores to the same bits).
        alpha = jax.device_put(alpha[: A.n], NamedSharding(A.mesh, P()))
        return KernelBlockLinearMapper(
            self.kernel, x_full[: A.n], alpha, self.predict_block_size
        )

    def _fit_dense(self, X, Y) -> KernelBlockLinearMapper:
        """Un-sharded fallback for non-Gaussian kernels: materialize K once
        and solve directly (fine at the sample sizes such kernels see)."""
        n = X.shape[0]
        K = self.kernel.block(X, X)
        alpha = jnp.linalg.solve(
            K + self.lam * jnp.eye(n, dtype=X.dtype), Y
        )
        self.last_cg_iters = 0
        return KernelBlockLinearMapper(
            self.kernel, X, alpha, self.predict_block_size
        )
