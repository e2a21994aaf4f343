"""Distributed linalg vs NumPy oracles on the 8-device CPU mesh.

Mirrors the reference's solver test strategy: small fixed-seed systems
checked against direct solves (SURVEY.md §4).
"""

import numpy as np
import pytest

from keystone_tpu.linalg import (
    RowMatrix,
    block_coordinate_descent,
    solve_least_squares_normal,
    solve_least_squares_tsqr,
    tsqr_r,
)
from keystone_tpu.linalg.bcd import assemble_blocks


def _problem(rng, n=200, d=24, k=3):
    A = rng.normal(size=(n, d)).astype(np.float32)
    W_true = rng.normal(size=(d, k)).astype(np.float32)
    B = A @ W_true + 0.01 * rng.normal(size=(n, k)).astype(np.float32)
    return A, B, W_true


def _ridge_oracle(A, B, lam):
    d = A.shape[1]
    return np.linalg.solve(
        A.astype(np.float64).T @ A.astype(np.float64) + lam * np.eye(d),
        A.astype(np.float64).T @ B.astype(np.float64),
    )


def test_from_array_pads_and_collects(rng):
    A = rng.normal(size=(13, 4)).astype(np.float32)
    M = RowMatrix.from_array(A)
    assert M.padded_rows % M.num_shards == 0
    assert M.shape == (13, 4)
    np.testing.assert_allclose(M.collect(), A)


def test_gram_matches_numpy(rng):
    A = rng.normal(size=(100, 8)).astype(np.float32)
    M = RowMatrix.from_array(A)
    np.testing.assert_allclose(M.gram(), A.T @ A, rtol=1e-5, atol=1e-4)


def test_atb_matches_numpy(rng):
    A = rng.normal(size=(57, 6)).astype(np.float32)
    B = rng.normal(size=(57, 3)).astype(np.float32)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    np.testing.assert_allclose(Ma.atb(Mb), A.T @ B, rtol=1e-5, atol=1e-4)


def test_matmul_row_sharded(rng):
    A = rng.normal(size=(30, 5)).astype(np.float32)
    W = rng.normal(size=(5, 2)).astype(np.float32)
    out = RowMatrix.from_array(A).matmul(W)
    np.testing.assert_allclose(out.collect(), A @ W, rtol=1e-5, atol=1e-5)


def test_tsqr_r_reproduces_gram(rng):
    # R is unique up to signs; RᵀR must equal AᵀA.
    A = rng.normal(size=(160, 12)).astype(np.float32)
    R = np.asarray(tsqr_r(RowMatrix.from_array(A)))
    np.testing.assert_allclose(R.T @ R, A.T @ A, rtol=1e-4, atol=1e-3)


def test_tsqr_r_short_shards(rng):
    # Local shard rows (24/8 = 3) < d = 5 exercises the R padding path.
    A = rng.normal(size=(24, 5)).astype(np.float32)
    R = np.asarray(tsqr_r(RowMatrix.from_array(A)))
    np.testing.assert_allclose(R.T @ R, A.T @ A, rtol=1e-4, atol=1e-3)


def test_normal_equations_solve(rng):
    A, B, _ = _problem(rng)
    lam = 0.1
    W = solve_least_squares_normal(
        RowMatrix.from_array(A), RowMatrix.from_array(B), lam
    )
    np.testing.assert_allclose(W, _ridge_oracle(A, B, lam), rtol=1e-3, atol=1e-3)


def test_tsqr_solve_matches_lstsq(rng):
    A, B, _ = _problem(rng)
    W = solve_least_squares_tsqr(RowMatrix.from_array(A), RowMatrix.from_array(B))
    oracle = np.linalg.lstsq(A.astype(np.float64), B.astype(np.float64), rcond=None)[0]
    np.testing.assert_allclose(W, oracle, rtol=1e-3, atol=1e-3)


def test_tsqr_solve_with_ridge(rng):
    A, B, _ = _problem(rng)
    lam = 0.5
    W = solve_least_squares_tsqr(
        RowMatrix.from_array(A), RowMatrix.from_array(B), lam
    )
    np.testing.assert_allclose(W, _ridge_oracle(A, B, lam), rtol=1e-3, atol=1e-3)


def test_bcd_single_block_equals_normal_equations(rng):
    A, B, _ = _problem(rng)
    lam = 0.2
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    W_blocks, blocks = block_coordinate_descent(
        Ma, Mb, block_size=A.shape[1], num_iters=1, lam=lam
    )
    assert blocks == [(0, A.shape[1])]
    np.testing.assert_allclose(
        assemble_blocks(W_blocks),
        _ridge_oracle(A, B, lam),
        rtol=1e-3,
        atol=1e-3,
    )


def test_bcd_converges_to_direct_solution(rng):
    A, B, _ = _problem(rng, n=400, d=32)
    lam = 0.1
    W_blocks, blocks = block_coordinate_descent(
        RowMatrix.from_array(A),
        RowMatrix.from_array(B),
        block_size=8,
        num_iters=30,
        lam=lam,
    )
    W = np.asarray(assemble_blocks(W_blocks))
    oracle = _ridge_oracle(A, B, lam)
    np.testing.assert_allclose(W, oracle, rtol=2e-2, atol=2e-2)


def test_bcd_weighted_matches_weighted_oracle(rng):
    A, B, _ = _problem(rng)
    lam = 0.3
    w = rng.uniform(0.5, 2.0, size=A.shape[0]).astype(np.float32)
    W_blocks, blocks = block_coordinate_descent(
        RowMatrix.from_array(A),
        RowMatrix.from_array(B),
        block_size=A.shape[1],
        num_iters=1,
        lam=lam,
        row_weights=w,
    )
    Aw = A * w[:, None]
    d = A.shape[1]
    oracle = np.linalg.solve(
        Aw.astype(np.float64).T @ A.astype(np.float64) + lam * np.eye(d),
        Aw.astype(np.float64).T @ B.astype(np.float64),
    )
    np.testing.assert_allclose(
        assemble_blocks(W_blocks), oracle, rtol=1e-3, atol=1e-3
    )


def test_alignment_errors(rng):
    Ma = RowMatrix.from_array(rng.normal(size=(16, 3)))
    Mb = RowMatrix.from_array(rng.normal(size=(24, 3)))
    with pytest.raises(ValueError, match="share n"):
        Ma.atb(Mb)


def test_bcd_cached_grams_matches_uncached(rng):
    A, B, _ = _problem(rng, n=240, d=24)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    W_cached, blocks = block_coordinate_descent(
        Ma, Mb, block_size=8, num_iters=5, lam=0.2, cache_grams=True
    )
    W_plain, _ = block_coordinate_descent(
        Ma, Mb, block_size=8, num_iters=5, lam=0.2, cache_grams=False
    )
    from keystone_tpu.linalg.bcd import assemble_blocks

    np.testing.assert_allclose(
        assemble_blocks(W_cached),
        assemble_blocks(W_plain),
        rtol=1e-4,
        atol=1e-4,
    )


def test_bcd_batched_factor_ragged_and_chunked(rng):
    """Batched factor phase: ragged tail block + factor_batch smaller than
    the block count must still match the uncached solve digit-for-digit."""
    from keystone_tpu.config import config

    A, B, _ = _problem(rng, d=26)  # blocks of 8 -> 3 equal + ragged 2-wide
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    old = config.factor_batch
    config.factor_batch = 2  # forces two batched chunks + tail path
    try:
        W_c, _ = block_coordinate_descent(
            Ma, Mb, block_size=8, num_iters=4, lam=0.2, cache_grams=True
        )
    finally:
        config.factor_batch = old
    W_p, _ = block_coordinate_descent(
        Ma, Mb, block_size=8, num_iters=4, lam=0.2, cache_grams=False
    )
    np.testing.assert_allclose(
        assemble_blocks(W_c), assemble_blocks(W_p), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize(
    "b,leaf,levels,batch",
    [
        (13, 1024, 0, (3,)),  # one leaf: the unblocked path, at the default
        (13, 5, 2, (3,)),  # leaves narrower than a tile: the plain half
        (300, 128, 2, (2,)),  # 128 + (128 + 44): b no multiple of the leaf
        (300, 128, 2, ()),  # unbatched
        (1100, 300, 3, (2,)),  # 512 + 588, 588 = 256 + 332, 332 = 128 + 204
        (1100, 300, 3, ()),
    ],
)
def test_spd_inv_blocked_matches_one_leaf_and_oracle(rng, b, leaf, levels, batch):
    """The blocked inverse (a triangular inverse and a triangular product
    by halves) must equal the one-leaf inverse (one triangular solve, one
    dense product: what every toy width runs) and the float64 oracle, with
    ragged halves and with or without the batched leading axis."""
    import jax.numpy as jnp

    from keystone_tpu.linalg.bcd import _batched_spd_inv, _inv_levels

    assert _inv_levels(b, leaf) == levels
    X = rng.normal(size=batch + (b, b)).astype(np.float32)
    grams = X @ np.swapaxes(X, -1, -2) / b + 2.0 * np.eye(b, dtype=np.float32)
    one = np.asarray(_batched_spd_inv(jnp.asarray(grams), leaf=b))
    blocked = np.asarray(_batched_spd_inv(jnp.asarray(grams), leaf=leaf))
    assert blocked.shape == grams.shape
    np.testing.assert_allclose(blocked, one, rtol=1e-5, atol=1e-5)
    oracle = np.linalg.inv(grams.astype(np.float64))
    np.testing.assert_allclose(blocked, oracle, rtol=1e-3, atol=1e-3)
    # S12 is S21's mirror: the inverse is symmetric to the bit.
    np.testing.assert_array_equal(blocked, np.swapaxes(blocked, -1, -2))


def test_spd_inv_blocked_holds_the_residual_at_condition_1e6(rng):
    """On a float32 ridge gram of condition 1e6 (the cells' blocks read
    4e5) the blocked path's ‖A·inv − I‖ is no worse than twice the
    one-leaf path's."""
    import jax.numpy as jnp

    from keystone_tpu.linalg.bcd import _batched_spd_inv

    b = 512
    q, _ = np.linalg.qr(rng.normal(size=(b, b)))
    gram = ((q * np.logspace(0, -6, b)) @ q.T).astype(np.float32)
    gram = (gram + gram.T) / 2
    assert 5e5 < np.linalg.cond(gram.astype(np.float64)) < 2e6

    def residual(leaf):
        inv = np.asarray(_batched_spd_inv(jnp.asarray(gram), leaf=leaf), np.float64)
        return np.linalg.norm(gram.astype(np.float64) @ inv - np.eye(b))

    assert residual(64) <= 2.0 * residual(b)


def test_spd_inv_blocked_skips_the_zeros():
    """A count, not a speed: at b = 1024 the blocked path's compiled
    program multiplies 4b³/3 and a leaf's share where one leaf's dense YᵀY
    alone is 2b³. (The CPU's count leaves the triangular solve out, a LAPACK
    call, so the ratio here cannot pass under 2/3; with the solve's 2b³
    counted on the one-leaf side it is a third.)"""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.linalg.bcd import _batched_spd_inv

    b = 1024
    gram = jax.ShapeDtypeStruct((b, b), jnp.float32)

    def flops(leaf):
        fn = jax.jit(lambda g: _batched_spd_inv(g, leaf=leaf))
        return fn.lower(gram).compile().cost_analysis()["flops"]

    one, blocked = flops(b), flops(128)
    assert one >= 2 * b**3
    assert blocked < 1.45 * b**3 and blocked < 0.72 * one


def test_bcd_cached_grams_weighted(rng):
    A, B, _ = _problem(rng)
    w = rng.uniform(0.5, 2.0, size=A.shape[0]).astype(np.float32)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    kwargs = dict(block_size=8, num_iters=3, lam=0.2, row_weights=w)
    W_c, blocks = block_coordinate_descent(Ma, Mb, cache_grams=True, **kwargs)
    W_p, _ = block_coordinate_descent(Ma, Mb, cache_grams=False, **kwargs)
    from keystone_tpu.linalg.bcd import assemble_blocks

    np.testing.assert_allclose(
        assemble_blocks(W_c), assemble_blocks(W_p),
        rtol=1e-4, atol=1e-4,
    )


def test_streamed_bcd_matches_device_resident(rng):
    from keystone_tpu.linalg import block_coordinate_descent_streamed

    A, B, _ = _problem(rng, n=240, d=32)
    Mb = RowMatrix.from_array(B)
    W_s, blocks = block_coordinate_descent_streamed(
        A, Mb, block_size=8, num_iters=4, lam=0.2
    )
    Ma = RowMatrix.from_array(A)
    W_d, _ = block_coordinate_descent(
        Ma, RowMatrix.from_array(B), block_size=8, num_iters=4, lam=0.2
    )
    np.testing.assert_allclose(
        assemble_blocks(W_s), assemble_blocks(W_d),
        rtol=1e-4, atol=1e-4,
    )


def test_streamed_bcd_weighted_and_row_mismatch(rng):
    from keystone_tpu.linalg import block_coordinate_descent_streamed

    A, B, _ = _problem(rng)
    w = rng.uniform(0.5, 2.0, size=A.shape[0]).astype(np.float32)
    Mb = RowMatrix.from_array(B)
    W_s, blocks = block_coordinate_descent_streamed(
        A, Mb, block_size=8, num_iters=2, lam=0.1, row_weights=w
    )
    Ma = RowMatrix.from_array(A)
    W_d, _ = block_coordinate_descent(
        Ma, RowMatrix.from_array(B), block_size=8, num_iters=2, lam=0.1,
        row_weights=w,
    )
    np.testing.assert_allclose(
        assemble_blocks(W_s), assemble_blocks(W_d),
        rtol=1e-4, atol=1e-4,
    )
    with pytest.raises(ValueError, match="must match B rows"):
        block_coordinate_descent_streamed(A[:10], Mb, 8, 1)


def test_normal_equations_refinement_reduces_system_residual(rng):
    # Refinement corrects the factorization/solve error of the f32 Cholesky
    # (it cannot fix f32 gram *formation* error, the other error source):
    # the residual of the regularized normal-equation system must not grow
    # and the solution must stay at the oracle within f32 tolerances.
    n, d = 400, 24
    U = rng.normal(size=(n, d)).astype(np.float32)
    scales = np.logspace(0, -3.5, d).astype(np.float32)
    A = U * scales
    B = rng.normal(size=(n, 2)).astype(np.float32)
    lam = 1e-6
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    reg = (
        np.asarray(Ma.gram(), dtype=np.float64) + lam * np.eye(d)
    )
    atb = np.asarray(Ma.atb(Mb), dtype=np.float64)

    def sys_resid(w):
        return np.linalg.norm(reg @ w.astype(np.float64) - atb)

    w0 = np.asarray(solve_least_squares_normal(Ma, Mb, lam, refine_steps=0))
    w2 = np.asarray(solve_least_squares_normal(Ma, Mb, lam, refine_steps=2))
    assert sys_resid(w2) <= sys_resid(w0) * 1.5
    oracle = _ridge_oracle(A, B, lam)
    np.testing.assert_allclose(
        w2, oracle, rtol=1e-3, atol=1e-3
    )


def test_streamed_bcd_checkpoint_resume(rng, tmp_path):
    from keystone_tpu.linalg import block_coordinate_descent_streamed

    A, B, _ = _problem(rng, n=160, d=16)
    Mb = RowMatrix.from_array(B)
    ck = str(tmp_path / "sbcd")
    W_ref, blocks = block_coordinate_descent_streamed(A, Mb, 8, 4, lam=0.1)
    block_coordinate_descent_streamed(A, Mb, 8, 2, lam=0.1, checkpoint_dir=ck)
    W_res, _ = block_coordinate_descent_streamed(
        A, Mb, 8, 4, lam=0.1, checkpoint_dir=ck
    )
    np.testing.assert_allclose(
        assemble_blocks(W_res), assemble_blocks(W_ref),
        rtol=1e-4, atol=1e-4,
    )


def test_chunked_normal_equations_matches_full_solve(rng):
    from keystone_tpu.linalg import solve_least_squares_chunked
    from keystone_tpu.loaders.stream import BatchIterator

    A, B, _ = _problem(rng, n=500, d=16)
    lam = 0.2
    batches = BatchIterator.from_arrays(A, B, batch_rows=128)
    W = np.asarray(solve_least_squares_chunked(batches, lam=lam))
    np.testing.assert_allclose(W, _ridge_oracle(A, B, lam), rtol=1e-3, atol=1e-3)
    with pytest.raises(ValueError, match="empty"):
        solve_least_squares_chunked(iter([]), lam=lam)


def test_batch_iterator_csv_and_map(rng, tmp_path):
    from keystone_tpu.loaders.stream import BatchIterator

    X = rng.normal(size=(10, 3)).astype(np.float32)
    y = rng.integers(0, 2, 10)
    path = tmp_path / "d.csv"
    with open(path, "w") as f:
        for i in range(10):
            f.write(",".join([str(y[i])] + [f"{v:.6f}" for v in X[i]]) + "\n")
    it = BatchIterator.from_csv(str(path), label_col=0, batch_rows=4)
    chunks = list(it)
    assert [c[0].shape[0] for c in chunks] == [4, 4, 2]
    np.testing.assert_allclose(np.concatenate([c[0] for c in chunks]), X, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate([c[1] for c in chunks]), y)
    doubled = list(it.map_batches(lambda b: b * 2))
    np.testing.assert_allclose(doubled[0][0], chunks[0][0] * 2, atol=1e-6)
    # Re-iterable (a second pass yields the same data).
    assert len(list(it)) == 3


def test_checkpoint_resumes_across_device_and_streamed_paths(rng, tmp_path):
    # Fingerprints must agree between the two paths so a solve checkpointed
    # on one can resume on the other (n chosen NOT divisible by 8 shards so
    # the padded last row differs from the logical last row).
    from keystone_tpu.linalg import block_coordinate_descent_streamed

    A, B, _ = _problem(rng, n=150, d=16)
    ck = str(tmp_path / "xpath")
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    W_ref, blocks = block_coordinate_descent(Ma, Mb, 8, 4, lam=0.1)
    block_coordinate_descent(Ma, Mb, 8, 2, lam=0.1, checkpoint_dir=ck)
    W_res, _ = block_coordinate_descent_streamed(
        A, RowMatrix.from_array(B), 8, 4, lam=0.1, checkpoint_dir=ck
    )
    np.testing.assert_allclose(
        assemble_blocks(W_res), assemble_blocks(W_ref),
        rtol=1e-4, atol=1e-4,
    )


def test_gram_and_atb_fused(rng):
    A = rng.normal(size=(90, 7)).astype(np.float32)
    B = rng.normal(size=(90, 2)).astype(np.float32)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    g, ab = Ma.gram_and_atb(Mb)
    np.testing.assert_allclose(g, A.T @ A, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(ab, A.T @ B, rtol=1e-5, atol=1e-4)


# -- fused scan path vs legacy per-block loop --------------------------------


def _both_paths(rng, **kwargs):
    from keystone_tpu.config import config

    A, B, _ = _problem(rng, n=240, d=32)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    prior = config.fused_epochs  # restore whatever the caller had set
    try:
        config.fused_epochs = None  # auto: fused (blocks tile d)
        W_f, blocks = block_coordinate_descent(Ma, Mb, **kwargs)
        config.fused_epochs = False
        W_l, _ = block_coordinate_descent(Ma, Mb, **kwargs)
    finally:
        config.fused_epochs = prior
    return A, B, W_f, W_l, blocks


def test_fused_matches_legacy_cached(rng):
    A, B, W_f, W_l, blocks = _both_paths(
        rng, block_size=8, num_iters=4, lam=0.15, cache_grams=True
    )
    assert len(blocks) == 4
    np.testing.assert_allclose(
        assemble_blocks(W_f), assemble_blocks(W_l), rtol=1e-4, atol=1e-4
    )
    # And both agree with the direct ridge oracle after enough epochs.
    W_oracle = _ridge_oracle(A, B, 0.15)
    np.testing.assert_allclose(
        assemble_blocks(W_f), W_oracle, rtol=5e-2, atol=5e-2
    )


def test_fused_matches_legacy_uncached(rng):
    _, _, W_f, W_l, _ = _both_paths(
        rng, block_size=16, num_iters=2, lam=0.3, cache_grams=False
    )
    np.testing.assert_allclose(
        assemble_blocks(W_f), assemble_blocks(W_l), rtol=1e-4, atol=1e-4
    )


def test_fused_matches_legacy_weighted(rng):
    from keystone_tpu.config import config

    A, B, _ = _problem(rng, n=160, d=16)
    w = (1.0 + rng.uniform(size=(160,))).astype(np.float32)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    kwargs = dict(block_size=8, num_iters=3, lam=0.2, row_weights=w)
    W_f, _ = block_coordinate_descent(Ma, Mb, **kwargs)
    config.fused_epochs = False
    try:
        W_l, _ = block_coordinate_descent(Ma, Mb, **kwargs)
    finally:
        config.fused_epochs = None
    np.testing.assert_allclose(
        assemble_blocks(W_f), assemble_blocks(W_l), rtol=1e-4, atol=1e-4
    )


def test_fused_single_block_and_ragged_fallback(rng):
    # nb=1 exercises the scan's degenerate length; ragged d falls back to
    # the legacy loop (same answer either way).
    A, B, _ = _problem(rng, n=120, d=20)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    W1, blocks1 = block_coordinate_descent(
        Ma, Mb, block_size=20, num_iters=2, lam=0.1
    )
    assert len(blocks1) == 1
    W2, blocks2 = block_coordinate_descent(
        Ma, Mb, block_size=12, num_iters=6, lam=0.1  # ragged: 12 + 8
    )
    assert [e - s for s, e in blocks2] == [12, 8]
    W_oracle = _ridge_oracle(A, B, 0.1)
    np.testing.assert_allclose(
        assemble_blocks(W2), W_oracle, rtol=5e-2, atol=5e-2
    )


def test_fused_checkpoint_resume_across_paths(rng, tmp_path):
    """A fused solve checkpoints per epoch with the same fingerprint as the
    legacy loop: 2 epochs fused + resume to 4 == 4 epochs straight (legacy),
    in either direction."""
    from keystone_tpu.config import config

    A, B, _ = _problem(rng, n=120, d=16)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    kwargs = dict(block_size=8, lam=0.1)
    W_ref, _ = block_coordinate_descent(Ma, Mb, num_iters=4, **kwargs)

    ck = str(tmp_path / "ck")
    block_coordinate_descent(
        Ma, Mb, num_iters=2, checkpoint_dir=ck, **kwargs
    )
    config.fused_epochs = False  # resume the fused checkpoint on the legacy path
    try:
        W_res, _ = block_coordinate_descent(
            Ma, Mb, num_iters=4, checkpoint_dir=ck, **kwargs
        )
    finally:
        config.fused_epochs = None
    np.testing.assert_allclose(
        assemble_blocks(W_res), assemble_blocks(W_ref), rtol=1e-4, atol=1e-4
    )


def test_fused_factor_chunking_matches_whole_batch(rng):
    """config.factor_batch bounds the fused factor phase's transient (and
    forces per-block factorization on request) without changing results."""
    from keystone_tpu.config import config

    A, B, _ = _problem(rng, n=200, d=32)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    kwargs = dict(block_size=8, num_iters=3, lam=0.2, cache_grams=True)
    W_whole, _ = block_coordinate_descent(Ma, Mb, **kwargs)  # auto chunk
    config.factor_batch = 2  # 4 blocks → two chunked factor programs
    try:
        W_chunk, _ = block_coordinate_descent(Ma, Mb, **kwargs)
    finally:
        config.factor_batch = None
    np.testing.assert_allclose(
        assemble_blocks(W_whole), assemble_blocks(W_chunk), rtol=1e-5, atol=1e-5
    )
