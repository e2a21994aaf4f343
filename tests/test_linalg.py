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


def _bcd_oracle(A, B, block_size, num_iters, lam, w=None):
    """Plain block coordinate descent in float64: blocks in order, one
    ``np.linalg.solve`` a visit, optional row weights. The reference the
    solver's tests compare against; it shares nothing with ``bcd.py``."""
    A, B = A.astype(np.float64), B.astype(np.float64)
    d = A.shape[1]
    Aw = A if w is None else A * w.astype(np.float64)[:, None]
    blocks = [(s, min(s + block_size, d)) for s in range(0, d, block_size)]
    W, R = np.zeros((d, B.shape[1])), B.copy()
    for _ in range(num_iters):
        for s, e in blocks:
            R += A[:, s:e] @ W[s:e]
            gram = Aw[:, s:e].T @ A[:, s:e] + lam * np.eye(e - s)
            W[s:e] = np.linalg.solve(gram, Aw[:, s:e].T @ R)
            R -= A[:, s:e] @ W[s:e]
    return W, blocks


def _assert_matches_oracle(W_blocks, blocks, A, B, block_size, num_iters, lam,
                           w=None):
    """Weights, block ranges and block widths against ``_bcd_oracle``: the
    widths are the true ones, so no pad column reaches the caller."""
    W_oracle, blocks_oracle = _bcd_oracle(A, B, block_size, num_iters, lam, w)
    assert blocks == blocks_oracle
    assert [w_b.shape[0] for w_b in W_blocks] == [e - s for s, e in blocks]
    np.testing.assert_allclose(
        assemble_blocks(W_blocks), W_oracle, rtol=1e-4, atol=1e-4
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


@pytest.mark.parametrize("chunk", [2, 3])
def test_bcd_batched_factor_ragged_and_chunked(rng, monkeypatch, chunk):
    """A ragged last block under a factor chunk smaller than the block count
    (what the chip runs; the CPU's own chunk is 1): 26 columns in blocks of
    8 are 3 whole blocks and a padded one of 2, which chunk 2 factors beside
    a whole block and chunk 3 alone."""
    from keystone_tpu.linalg import bcd

    A, B, _ = _problem(rng, d=26)
    monkeypatch.setattr(bcd, "_factor_chunk", lambda b: chunk)
    W_c, blocks = block_coordinate_descent(
        RowMatrix.from_array(A), RowMatrix.from_array(B),
        block_size=8, num_iters=4, lam=0.2, cache_grams=True,
    )
    _assert_matches_oracle(W_c, blocks, A, B, 8, 4, 0.2)


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


# -- the in-HBM solve body against the numpy oracle --------------------------


@pytest.mark.parametrize(
    "kwargs,weighted",
    [
        (dict(block_size=8, num_iters=4, lam=0.15, cache_grams=True), False),
        (dict(block_size=16, num_iters=2, lam=0.3, cache_grams=False), False),
        (dict(block_size=8, num_iters=3, lam=0.2), True),
    ],
    ids=["cached", "uncached", "weighted"],
)
def test_solve_matches_oracle(rng, kwargs, weighted):
    A, B, _ = _problem(rng, n=240, d=32)
    w = (1.0 + rng.uniform(size=(240,))).astype(np.float32) if weighted else None
    W, blocks = block_coordinate_descent(
        RowMatrix.from_array(A), RowMatrix.from_array(B), row_weights=w,
        **kwargs,
    )
    _assert_matches_oracle(
        W, blocks, A, B, kwargs["block_size"], kwargs["num_iters"],
        kwargs["lam"], w,
    )


@pytest.mark.parametrize("lam", [0.0, 0.1])
@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weighted"])
@pytest.mark.parametrize("cache_grams", [True, False], ids=["cached", "uncached"])
@pytest.mark.parametrize(
    "d,block_size,widths",
    [
        (24, 128, [24]),  # one block wider than d: it is d wide, no pad
        (20, 12, [12, 8]),  # a ragged tail, padded to 12 inside the solve
        (26, 8, [8, 8, 8, 2]),
    ],
)
def test_single_block_and_ragged_tail_run_the_one_body(
    rng, d, block_size, widths, cache_grams, weighted, lam
):
    """A block wider than d and a ragged last block run the body every
    other shape runs, λ = 0 included (the pad's diagonal is 1, not λ), and
    the pad columns never reach the returned W."""
    A, B, _ = _problem(rng, n=120, d=d)
    w = rng.uniform(0.5, 2.0, size=120).astype(np.float32) if weighted else None
    W, blocks = block_coordinate_descent(
        RowMatrix.from_array(A), RowMatrix.from_array(B),
        block_size=block_size, num_iters=3, lam=lam, row_weights=w,
        cache_grams=cache_grams,
    )
    assert [w_b.shape[0] for w_b in W] == widths
    _assert_matches_oracle(W, blocks, A, B, block_size, 3, lam, w)


@pytest.mark.parametrize("cached", [True, False], ids=["cached", "uncached"])
def test_pad_columns_keep_zero_weights(rng, cached):
    """Inside the solve the padded block's pad rows of W stay exactly 0 at
    λ = 0: the padded gram is block diagonal with 1 on the pad's diagonal,
    and so is its inverse."""
    import jax.numpy as jnp

    from keystone_tpu.config import config
    from keystone_tpu.linalg import bcd

    A, B, _ = _problem(rng, n=120, d=20)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    mesh, axis = Ma.mesh, config.data_axis
    nb, b, pad, k = 2, 12, 4, B.shape[1]
    precision, fold = bcd._precision(), bcd.fold_blocks(mesh.shape[axis])
    lam = jnp.zeros((), jnp.float32)
    w_rows = jnp.zeros((Ma.padded_rows,), jnp.float32)
    a3 = bcd._stack_blocks_fn(mesh, axis, nb, pad)(Ma.data)
    assert a3.shape == (nb, Ma.padded_rows, b)
    np.testing.assert_array_equal(np.asarray(a3[1, :, b - pad:]), 0.0)
    if cached:
        invs = bcd._fused_factor_fn(mesh, axis, precision, False, fold, pad)(
            a3, lam, w_rows)
        np.testing.assert_array_equal(
            np.asarray(invs[1, b - pad:, b - pad:]), np.eye(pad))
        np.testing.assert_array_equal(np.asarray(invs[1, : b - pad, b - pad:]), 0.0)
    else:
        invs = jnp.zeros((nb, 1, 1), jnp.float32)
    step = bcd._fused_epochs_fn(
        mesh, axis, precision, False, 3, cached, fold, 0 if cached else pad)
    _, W3 = step(a3, invs, jnp.array(Mb.data), jnp.zeros((nb, b, k)), lam, w_rows)
    np.testing.assert_array_equal(np.asarray(W3[1, b - pad:]), 0.0)
    W_oracle, _ = _bcd_oracle(A, B, b, 3, 0.0)
    np.testing.assert_allclose(
        np.asarray(W3).reshape(nb * b, k)[:20], W_oracle, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "d,block_size,widths", [(16, 8, [8, 8]), (20, 12, [12, 8])],
    ids=["tiled", "ragged"],
)
def test_checkpoint_resume(rng, tmp_path, d, block_size, widths):
    """2 epochs under ``checkpoint_dir`` + resume to 4 == 4 epochs straight
    == the oracle's 4; the checkpoint holds every block at its true width
    (what a solve before the padding wrote, and resumes from); a solve
    resumed at its last epoch returns what it restored."""
    import orbax.checkpoint as ocp

    A, B, _ = _problem(rng, n=120, d=d)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    kwargs = dict(block_size=block_size, lam=0.1)
    W_ref, _ = block_coordinate_descent(Ma, Mb, num_iters=4, **kwargs)

    ck = str(tmp_path / "ck")
    block_coordinate_descent(Ma, Mb, num_iters=2, checkpoint_dir=ck, **kwargs)
    saved = ocp.PyTreeCheckpointer().restore(str(tmp_path / "ck" / "epoch_2"))
    assert [w_b.shape for w_b in saved["W"]] == [(n, B.shape[1]) for n in widths]
    W_res, blocks = block_coordinate_descent(
        Ma, Mb, num_iters=4, checkpoint_dir=ck, **kwargs
    )
    np.testing.assert_allclose(
        assemble_blocks(W_res), assemble_blocks(W_ref), rtol=1e-4, atol=1e-4
    )
    _assert_matches_oracle(W_res, blocks, A, B, block_size, 4, 0.1)
    W_again, _ = block_coordinate_descent(
        Ma, Mb, num_iters=4, checkpoint_dir=ck, **kwargs
    )
    np.testing.assert_array_equal(
        assemble_blocks(W_again), assemble_blocks(W_res)
    )


def test_factor_chunking_matches_whole_batch(rng, monkeypatch):
    """The factor chunk bounds the factor phase's transient without
    changing results: four blocks in one program, and in two programs of
    two whose inverses are concatenated."""
    from keystone_tpu.linalg import bcd

    A, B, _ = _problem(rng, n=200, d=32)
    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    kwargs = dict(block_size=8, num_iters=3, lam=0.2, cache_grams=True)
    monkeypatch.setattr(bcd, "_factor_chunk", lambda b: 16)
    W_whole, blocks = block_coordinate_descent(Ma, Mb, **kwargs)
    monkeypatch.setattr(bcd, "_factor_chunk", lambda b: 2)
    W_chunk, _ = block_coordinate_descent(Ma, Mb, **kwargs)
    np.testing.assert_allclose(
        assemble_blocks(W_whole), assemble_blocks(W_chunk), rtol=1e-5, atol=1e-5
    )
    _assert_matches_oracle(W_chunk, blocks, A, B, 8, 3, 0.2)


@pytest.mark.parametrize(
    "nb,b,k,n,weighted,epochs,chunk",
    [
        # imagenet-fit: 8 blocks, weighted, 3 epochs, chunk 2: four factor
        # programs and the concatenate.
        (8, 8, 10, 128, True, 3, 2),
        # timit-fit: 40 blocks, n the block's width, 5 epochs, chunk 8.
        (40, 8, 5, 8, False, 5, 8),
    ],
    ids=["imagenet-fit", "timit-fit"],
)
def test_solve_at_the_cells_block_structure(
    rng, monkeypatch, nb, b, k, n, weighted, epochs, chunk
):
    """The path both benchmark cells run (blocks tile d, cached inverses,
    a chunked factor phase, one epochs program), at the cells' block counts,
    epochs and chunks and tiny widths, through the rule that picks
    ``cache_grams`` itself."""
    from keystone_tpu.linalg import bcd

    A, B, _ = _problem(rng, n=n, d=nb * b, k=k)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32) if weighted else None
    monkeypatch.setattr(bcd, "_factor_chunk", lambda _: chunk)
    dispatched = []  # blocks in each factor program the solve dispatches
    factor_fn = bcd._fused_factor_fn

    def counting_factor_fn(*key):
        def factor(a3, lam, w_rows):
            dispatched.append(a3.shape[0])
            return factor_fn(*key)(a3, lam, w_rows)

        return factor

    monkeypatch.setattr(bcd, "_fused_factor_fn", counting_factor_fn)
    W, blocks = block_coordinate_descent(
        RowMatrix.from_array(A), RowMatrix.from_array(B),
        block_size=b, num_iters=epochs, lam=0.1, row_weights=w,
    )
    assert dispatched == [chunk] * (nb // chunk)
    _assert_matches_oracle(W, blocks, A, B, b, epochs, 0.1, w)
