"""Learning-node tests: solvers vs oracles, statistical models vs
recoverable synthetic structure (SURVEY.md §4 test strategy)."""

import numpy as np
import pytest

from keystone_tpu.nodes.learning import (
    BlockLeastSquaresEstimator,
    BlockWeightedLeastSquaresEstimator,
    DistributedPCAEstimator,
    GaussianKernelGenerator,
    GaussianMixtureModelEstimator,
    KernelRidgeCG,
    KMeansPlusPlusEstimator,
    LeastSquaresEstimator,
    LinearDiscriminantAnalysis,
    LinearMapEstimator,
    LogisticRegressionEstimator,
    NaiveBayesEstimator,
    PCAEstimator,
    ZCAWhitenerEstimator,
    choose_solver,
)


# ---------------------------------------------------------------------- block LS


def _ridge_with_intercept_oracle(X, Y, lam):
    Xc = X - X.mean(axis=0)
    Yc = Y - Y.mean(axis=0)
    d = X.shape[1]
    W = np.linalg.solve(Xc.T @ Xc + lam * np.eye(d), Xc.T @ Yc)
    b = Y.mean(axis=0) - X.mean(axis=0) @ W
    return W, b


def test_block_least_squares_converges(rng):
    X = rng.normal(size=(300, 24)).astype(np.float32)
    W_true = rng.normal(size=(24, 4)).astype(np.float32)
    Y = X @ W_true + 0.5
    model = BlockLeastSquaresEstimator(block_size=8, num_iters=25, lam=0.05).fit(
        X, Y
    )
    W, b = _ridge_with_intercept_oracle(
        X.astype(np.float64), Y.astype(np.float64), 0.05
    )
    np.testing.assert_allclose(np.asarray(model.W), W, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(model.b), b, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(model(X), X @ W + b, rtol=2e-2, atol=5e-2)


def test_block_weighted_upweights_rare_class(rng):
    # Highly imbalanced two-class problem: balanced weighting must improve
    # the rare class's margin vs the unweighted solve.
    n_major, n_minor = 500, 25
    X = np.concatenate(
        [
            rng.normal(loc=0.0, size=(n_major, 8)),
            rng.normal(loc=1.2, size=(n_minor, 8)),
        ]
    ).astype(np.float32)
    y = np.concatenate([np.zeros(n_major), np.ones(n_minor)]).astype(int)
    Y = (2 * np.eye(2)[y] - 1).astype(np.float32)
    unweighted = BlockLeastSquaresEstimator(block_size=8, num_iters=5, lam=0.1).fit(X, Y)
    weighted = BlockWeightedLeastSquaresEstimator(
        block_size=8, num_iters=5, lam=0.1, mixture_weight=1.0
    ).fit(X, Y)
    minor_scores_u = np.asarray(unweighted(X[n_major:]))[:, 1]
    minor_scores_w = np.asarray(weighted(X[n_major:]))[:, 1]
    assert minor_scores_w.mean() > minor_scores_u.mean()


def test_choose_solver_cost_model():
    assert choose_solver(100, 10, 3).name == "local"
    assert choose_solver(100_000, 4096, 10).name == "normal"
    assert choose_solver(1_000_000, 262_144, 1000).name == "block"


def test_least_squares_estimator_dispatches(rng):
    X = rng.normal(size=(50, 6)).astype(np.float32)
    Y = rng.normal(size=(50, 2)).astype(np.float32)
    est = LeastSquaresEstimator(lam=0.1)
    model = est.fit(X, Y)
    assert est.last_choice.name == "local"
    direct = LinearMapEstimator(lam=0.1).fit(X, Y)
    np.testing.assert_allclose(model.W, direct.W, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------- PCA/ZCA


def test_pca_matches_numpy_svd(rng):
    X = rng.normal(size=(100, 12)).astype(np.float32)
    pca = PCAEstimator(dims=4).fit(X)
    out = np.asarray(pca(X))
    Xc = X - X.mean(axis=0)
    _, _, vt = np.linalg.svd(Xc, full_matrices=False)
    oracle = Xc @ vt[:4].T
    # Components are sign-ambiguous; compare per-column up to sign.
    for j in range(4):
        diff = min(
            np.abs(out[:, j] - oracle[:, j]).max(),
            np.abs(out[:, j] + oracle[:, j]).max(),
        )
        assert diff < 1e-3


def test_distributed_pca_matches_local(rng):
    X = rng.normal(size=(160, 10)).astype(np.float32)
    p_local = PCAEstimator(dims=3).fit(X)
    p_dist = DistributedPCAEstimator(dims=3).fit(X)
    out_l = np.asarray(p_local(X))
    out_d = np.asarray(p_dist(X))
    for j in range(3):
        diff = min(
            np.abs(out_l[:, j] - out_d[:, j]).max(),
            np.abs(out_l[:, j] + out_d[:, j]).max(),
        )
        assert diff < 1e-3


def test_zca_whitens_covariance(rng):
    A = rng.normal(size=(6, 6))
    X = (rng.normal(size=(2000, 6)) @ A).astype(np.float32)
    zca = ZCAWhitenerEstimator(eps=1e-5).fit(X)
    out = np.asarray(zca(X))
    cov = out.T @ out / out.shape[0]
    np.testing.assert_allclose(cov, np.eye(6), atol=0.05)


# ---------------------------------------------------------------------- clustering


def test_kmeans_recovers_separated_clusters(rng):
    centers_true = np.array([[0, 0], [10, 0], [0, 10]], dtype=np.float32)
    X = np.concatenate(
        [c + rng.normal(scale=0.5, size=(100, 2)) for c in centers_true]
    ).astype(np.float32)
    model = KMeansPlusPlusEstimator(k=3, max_iters=20, seed=1).fit(X)
    found = np.asarray(model.centers)
    # Each true center has a found center within 0.5.
    for c in centers_true:
        assert np.min(np.linalg.norm(found - c, axis=1)) < 0.5
    onehot = np.asarray(model(X[:5]))
    assert onehot.shape == (5, 3)
    np.testing.assert_allclose(onehot.sum(axis=1), 1.0)


def test_gmm_recovers_mixture(rng):
    means_true = np.array([[-4.0, 0.0], [4.0, 2.0]])
    X = np.concatenate(
        [
            means_true[0] + rng.normal(scale=0.7, size=(300, 2)),
            means_true[1] + rng.normal(scale=1.2, size=(700, 2)),
        ]
    ).astype(np.float32)
    gmm = GaussianMixtureModelEstimator(k=2, max_iters=60, seed=0).fit(X)
    means = np.asarray(gmm.means)
    order = np.argsort(means[:, 0])
    np.testing.assert_allclose(means[order], means_true, atol=0.3)
    w = np.asarray(gmm.weights)[order]
    np.testing.assert_allclose(w, [0.3, 0.7], atol=0.05)
    resp = np.asarray(gmm(X[:4]))
    np.testing.assert_allclose(resp.sum(axis=1), 1.0, atol=1e-5)


# ---------------------------------------------------------------------- classifiers


def test_naive_bayes_hand_computation():
    X = np.array([[2, 0], [1, 1], [0, 3]], dtype=np.float32)
    y = np.array([0, 0, 1])
    model = NaiveBayesEstimator(num_classes=2, smoothing=1.0).fit(X, y)
    # priors: [2/3, 1/3]
    np.testing.assert_allclose(
        np.exp(np.asarray(model.log_prior)), [2 / 3, 1 / 3], atol=1e-6
    )
    # class 0 counts: [3, 1] + 1 → [4, 2]/6
    np.testing.assert_allclose(
        np.exp(np.asarray(model.log_likelihood))[0], [4 / 6, 2 / 6], atol=1e-6
    )
    scores = np.asarray(model(X))
    assert scores.shape == (3, 2)
    assert scores[0, 0] > scores[0, 1] and scores[2, 1] > scores[2, 0]


def test_logistic_regression_separable(rng):
    X = np.concatenate(
        [
            rng.normal(loc=-2.0, size=(200, 4)),
            rng.normal(loc=2.0, size=(200, 4)),
        ]
    ).astype(np.float32)
    y = np.concatenate([np.zeros(200), np.ones(200)]).astype(int)
    model = LogisticRegressionEstimator(num_classes=2, max_iters=50).fit(X, y)
    pred = np.argmax(np.asarray(model(X)), axis=1)
    assert (pred == y).mean() > 0.99


def test_lda_projects_classes_apart(rng):
    X = np.concatenate(
        [
            rng.normal(loc=[0, 0, 0, 0], size=(150, 4)),
            rng.normal(loc=[3, 0, 0, 0], size=(150, 4)),
        ]
    ).astype(np.float32)
    y = np.concatenate([np.zeros(150), np.ones(150)]).astype(int)
    proj = LinearDiscriminantAnalysis(dims=1).fit(X, y)
    z = np.asarray(proj(X)).ravel()
    gap = abs(z[:150].mean() - z[150:].mean())
    spread = 0.5 * (z[:150].std() + z[150:].std())
    # Two unit-variance clusters 3σ apart project to gap/spread ≈ 3.
    assert gap > 2.5 * spread


# ---------------------------------------------------------------------- kernel ridge


def test_kernel_ridge_matches_direct_solve(rng):
    n, d, k = 150, 5, 2
    X = rng.normal(size=(n, d)).astype(np.float32)
    Y = rng.normal(size=(n, k)).astype(np.float32)
    gamma, lam = 0.3, 0.1
    est = KernelRidgeCG(gamma=gamma, lam=lam, max_iters=400, tol=1e-7)
    model = est.fit(X, Y)
    # Direct dense oracle.
    sq = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
    K = np.exp(-gamma * sq)
    alpha = np.linalg.solve(K + lam * np.eye(n), Y.astype(np.float64))
    np.testing.assert_allclose(np.asarray(model.alpha), alpha, atol=1e-2)
    np.testing.assert_allclose(np.asarray(model(X)), K @ alpha, atol=1e-2)
    assert est.last_cg_iters is not None and est.last_cg_iters < 400


def test_kernel_ridge_interpolates_nonlinear_function(rng):
    X = np.linspace(-3, 3, 200).reshape(-1, 1).astype(np.float32)
    Y = np.sin(2 * X)
    model = KernelRidgeCG(gamma=2.0, lam=1e-4, max_iters=500).fit(X, Y)
    pred = np.asarray(model(X))
    assert np.abs(pred - Y).max() < 0.05


def test_kernel_ridge_dense_fallback_linear_kernel(rng):
    from keystone_tpu.nodes.learning import LinearKernelGenerator

    X = rng.normal(size=(60, 4)).astype(np.float32)
    Y = rng.normal(size=(60, 2)).astype(np.float32)
    model = KernelRidgeCG(kernel=LinearKernelGenerator(), lam=0.5).fit(X, Y)
    K = X @ X.T
    alpha = np.linalg.solve(K + 0.5 * np.eye(60), Y.astype(np.float64))
    np.testing.assert_allclose(np.asarray(model.alpha), alpha, atol=1e-2)


def test_kernel_ridge_rejects_kernel_plus_gamma():
    with pytest.raises(ValueError, match="not both"):
        KernelRidgeCG(kernel=GaussianKernelGenerator(1.0), gamma=2.0)


def test_block_ls_model_parallel_matches_data_parallel(rng):
    """parallelism='model' (d-sharded ring) reaches the same solution as
    the default data-parallel solve."""
    n, d, k = 256, 64, 3
    X = rng.normal(size=(n, d)).astype(np.float32)
    W_true = rng.normal(size=(d, k)).astype(np.float32)
    Y = X @ W_true + 0.25  # consistent system + intercept: oracle = Y
    kw = dict(block_size=16, num_iters=12, lam=1e-4)
    # Different sweep schedules converge at different rates, so compare
    # each to the exact answer rather than to each other mid-trajectory.
    for est in (
        BlockLeastSquaresEstimator(**kw),
        BlockLeastSquaresEstimator(**kw, parallelism="model"),
    ):
        pred = np.asarray(est.fit(X, Y).apply_batch(X))
        resid = np.linalg.norm(pred - Y) / np.linalg.norm(Y)
        assert resid < 5e-3, (est.parallelism, resid)


def test_block_ls_model_parallel_accepts_device_arrays(rng):
    """Regression: np.asarray over a jax.Array is a read-only zero-copy
    view, and the ring path's in-place intercept centering crashed on it
    (the executor device_puts every pipeline input, so this is the normal
    case, not the exotic one)."""
    import jax.numpy as jnp

    n, d, k = 128, 32, 2
    X = rng.normal(size=(n, d)).astype(np.float32)
    Y = (X @ rng.normal(size=(d, k)).astype(np.float32) + 0.5).astype(np.float32)
    est = BlockLeastSquaresEstimator(
        block_size=16, num_iters=8, lam=1e-4, parallelism="model"
    )
    pred = np.asarray(est.fit(jnp.asarray(X), jnp.asarray(Y)).apply_batch(X))
    resid = np.linalg.norm(pred - Y) / np.linalg.norm(Y)
    assert resid < 5e-3, resid


def test_block_ls_model_parallel_rejects_weights(rng):
    from keystone_tpu.nodes.learning import BlockWeightedLeastSquaresEstimator

    X = rng.normal(size=(64, 16)).astype(np.float32)
    Y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, size=64)]
    with pytest.raises(ValueError, match="weight"):
        BlockWeightedLeastSquaresEstimator(
            num_iters=2, lam=1e-3, parallelism="model"
        ).fit(X, Y)


def test_kernel_ridge_nystrom_preconditioner(rng):
    """PCG must (a) agree with the plain CG solution and (b) converge in
    strictly fewer iterations on an ill-conditioned RBF system (wide
    kernel, small lam) — the regime the preconditioner exists for."""
    n, d, k = 600, 12, 2
    X = rng.normal(size=(n, d)).astype(np.float32)
    Y = rng.normal(size=(n, k)).astype(np.float32)
    gamma, lam = 0.05, 1e-3
    plain = KernelRidgeCG(gamma=gamma, lam=lam, max_iters=500, tol=1e-4)
    m_plain = plain.fit(X, Y)
    pre = KernelRidgeCG(
        gamma=gamma, lam=lam, max_iters=500, tol=1e-4, precond_landmarks=200
    )
    m_pre = pre.fit(X, Y)
    # Same stopping rule, same operator: both land on the same system
    # solution within the residual tolerance.
    sq = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
    K = np.exp(-gamma * sq)
    for m in (m_plain, m_pre):
        resid = np.linalg.norm(
            (K + lam * np.eye(n)) @ np.asarray(m.alpha) - Y
        ) / np.linalg.norm(Y)
        assert resid < 1e-3
    assert pre.last_cg_iters < plain.last_cg_iters / 2


def test_kernel_ridge_preconditioned_padded_rows(rng):
    """n not divisible by the mesh: padded rows must stay inert under the
    preconditioner exactly as under plain CG."""
    n, d = 150, 5  # 150 % 8 != 0
    X = rng.normal(size=(n, d)).astype(np.float32)
    Y = rng.normal(size=(n, 2)).astype(np.float32)
    gamma, lam = 0.3, 0.1
    est = KernelRidgeCG(
        gamma=gamma, lam=lam, max_iters=400, tol=1e-7, precond_landmarks=64
    )
    model = est.fit(X, Y)
    sq = ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1)
    K = np.exp(-gamma * sq)
    alpha = np.linalg.solve(K + lam * np.eye(n), Y.astype(np.float64))
    np.testing.assert_allclose(np.asarray(model.alpha), alpha, atol=1e-2)


def test_block_weighted_matches_weighted_ridge_oracle(rng):
    # Full check incl. intercept: weighted centering must reproduce the
    # exact weighted-ridge-with-intercept optimum in the single-block case.
    X = rng.normal(size=(200, 10)).astype(np.float32) + 1.5
    y = (rng.uniform(size=200) < 0.2).astype(int)
    Y = (2 * np.eye(2)[y] - 1).astype(np.float32)
    est = BlockWeightedLeastSquaresEstimator(
        block_size=10, num_iters=1, lam=0.3, mixture_weight=1.0
    )
    model = est.fit(X, Y)
    w = np.asarray(est._weights(Y)).astype(np.float64)
    Xd, Yd = X.astype(np.float64), Y.astype(np.float64)
    xm = (w[:, None] * Xd).sum(0) / w.sum()
    ym = (w[:, None] * Yd).sum(0) / w.sum()
    Xc, Yc = Xd - xm, Yd - ym
    W = np.linalg.solve(
        (Xc * w[:, None]).T @ Xc + 0.3 * np.eye(10), (Xc * w[:, None]).T @ Yc
    )
    b = ym - xm @ W
    np.testing.assert_allclose(np.asarray(model.W), W, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(model.b), b, rtol=1e-3, atol=1e-3)


def test_block_ls_streaming_matches_device_path(rng):
    X = rng.normal(size=(300, 24)).astype(np.float32) + 0.5
    Y = rng.normal(size=(300, 4)).astype(np.float32)
    dev = BlockLeastSquaresEstimator(block_size=8, num_iters=3, lam=0.1, stream=False).fit(X, Y)
    str_ = BlockLeastSquaresEstimator(block_size=8, num_iters=3, lam=0.1, stream=True).fit(X, Y)
    np.testing.assert_allclose(np.asarray(str_.W), np.asarray(dev.W), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(str_.b), np.asarray(dev.b), rtol=1e-4, atol=1e-4)


def test_block_weighted_streaming_matches_device_path(rng):
    X = rng.normal(size=(240, 16)).astype(np.float32)
    y = (rng.uniform(size=240) < 0.2).astype(int)
    Y = (2 * np.eye(2)[y] - 1).astype(np.float32)
    kw = dict(block_size=8, num_iters=2, lam=0.2, mixture_weight=1.0)
    dev = BlockWeightedLeastSquaresEstimator(stream=False, **kw).fit(X, Y)
    str_ = BlockWeightedLeastSquaresEstimator(stream=True, **kw).fit(X, Y)
    np.testing.assert_allclose(np.asarray(str_.W), np.asarray(dev.W), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(str_.b), np.asarray(dev.b), rtol=1e-4, atol=1e-4)
