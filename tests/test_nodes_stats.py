"""Stats/util node unit tests vs direct NumPy computation (SURVEY.md §4)."""

import numpy as np

from keystone_tpu.nodes.stats import (
    CosineRandomFeatures,
    LinearRectifier,
    PaddedFFT,
    RandomSignNode,
    SignedHellingerMapper,
    StandardScaler,
    sample_columns,
    sample_rows,
)
from keystone_tpu.nodes.util import (
    Cast,
    ClassLabelIndicators,
    Identity,
    MaxClassifier,
    TopKClassifier,
    VectorCombiner,
    VectorSplitter,
)


def test_random_sign_node(rng):
    node = RandomSignNode.create(dim=16, seed=0)
    signs = np.asarray(node.signs)
    assert set(np.unique(signs)) <= {-1.0, 1.0}
    X = rng.normal(size=(4, 16)).astype(np.float32)
    np.testing.assert_allclose(node(X), X * signs)


def test_padded_fft_matches_numpy(rng):
    """Upstream's output: zero-padded to the next power of two, the real
    parts of the first n/2 bins, unscaled (784 pixels give 512)."""
    X = rng.normal(size=(3, 7)).astype(np.float32)
    out = np.asarray(PaddedFFT()(X))
    ref = np.fft.fft(np.pad(X.astype(np.float64), ((0, 0), (0, 1))), axis=-1)
    assert out.shape == (3, 4)
    np.testing.assert_allclose(out, ref[:, :4].real, atol=1e-5)
    wide = rng.normal(size=(2, 784)).astype(np.float32)
    got = np.asarray(PaddedFFT()(wide))
    assert got.shape == (2, 512)
    want = np.fft.fft(np.pad(wide.astype(np.float64), ((0, 0), (0, 240))), axis=-1)
    np.testing.assert_allclose(got, want[:, :512].real, rtol=1e-4, atol=1e-3)


def test_linear_rectifier():
    X = np.array([[-1.0, 0.5], [2.0, -3.0]], dtype=np.float32)
    np.testing.assert_allclose(LinearRectifier()(X), np.maximum(X, 0.0))
    np.testing.assert_allclose(
        LinearRectifier(max_val=0.1, alpha=0.5)(X), np.maximum(X - 0.5, 0.1)
    )


def test_standard_scaler(rng):
    X = rng.normal(loc=3.0, scale=2.0, size=(50, 4)).astype(np.float32)
    model = StandardScaler().fit(X)
    out = np.asarray(model(X))
    np.testing.assert_allclose(out.mean(axis=0), 0.0, atol=1e-5)
    np.testing.assert_allclose(out.std(axis=0, ddof=1), 1.0, atol=1e-4)


def test_cosine_random_features_range_and_shape(rng):
    node = CosineRandomFeatures.create(8, 32, gamma=0.5, seed=1)
    X = rng.normal(size=(5, 8)).astype(np.float32)
    out = np.asarray(node(X))
    assert out.shape == (5, 32)
    assert np.all(out >= -1.0) and np.all(out <= 1.0)
    ref = np.cos(X @ np.asarray(node.W) + np.asarray(node.b))
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_cosine_random_features_cauchy(rng):
    node = CosineRandomFeatures.create(4, 16, distribution="cauchy", seed=2)
    assert np.asarray(node.W).shape == (4, 16)


def test_signed_hellinger():
    X = np.array([[4.0, -9.0, 0.0]], dtype=np.float32)
    np.testing.assert_allclose(
        SignedHellingerMapper()(X), [[2.0, -3.0, 0.0]], atol=1e-6
    )


def test_samplers(rng):
    X = rng.normal(size=(20, 10))
    assert sample_rows(X, 5, seed=1).shape == (5, 10)
    assert sample_columns(X, 3, seed=1).shape == (20, 3)
    assert sample_rows(X, 50).shape == (20, 10)


def test_class_label_indicators():
    out = np.asarray(ClassLabelIndicators(4)(np.array([0, 2, 3])))
    expected = -np.ones((3, 4), dtype=np.float32)
    expected[0, 0] = expected[1, 2] = expected[2, 3] = 1.0
    np.testing.assert_allclose(out, expected)


def test_max_and_topk_classifier(rng):
    scores = np.array([[0.1, 0.9, 0.0], [0.5, 0.2, 0.8]], dtype=np.float32)
    np.testing.assert_array_equal(MaxClassifier()(scores), [1, 2])
    topk = np.asarray(TopKClassifier(2)(scores))
    np.testing.assert_array_equal(topk, [[1, 0], [2, 0]])


def test_vector_splitter_combiner(rng):
    X = rng.normal(size=(4, 10)).astype(np.float32)
    blocks = VectorSplitter(4)(X)
    assert [b.shape[-1] for b in blocks] == [4, 4, 2]
    np.testing.assert_allclose(VectorCombiner()(blocks), X, atol=1e-6)


def test_identity_and_cast(rng):
    X = rng.normal(size=(2, 3)).astype(np.float64)
    np.testing.assert_allclose(Identity()(X), X)
    assert np.asarray(Cast("float32")(X)).dtype == np.float32
