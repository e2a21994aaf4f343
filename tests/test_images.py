"""Image node tests vs direct NumPy loops (the reference's ConvolverSuite
strategy: compare against naive convolution; SURVEY.md §4)."""

import numpy as np
import pytest

from keystone_tpu.nodes.images import (
    CenterCornerPatcher,
    Convolver,
    GrayScaler,
    ImageVectorizer,
    PixelScaler,
    Pooler,
    RandomPatcher,
    SymmetricRectifier,
    Windower,
)
from keystone_tpu.nodes.learning import ZCAWhitenerEstimator
from keystone_tpu.utils.image import grayscale, metadata_of


def _naive_conv(X, F):
    n, h, w, c = X.shape
    nf, fh, fw, _ = F.shape
    oh, ow = h - fh + 1, w - fw + 1
    out = np.zeros((n, oh, ow, nf), dtype=np.float64)
    for i in range(oh):
        for j in range(ow):
            patch = X[:, i : i + fh, j : j + fw, :].reshape(n, -1)
            out[:, i, j, :] = patch @ F.reshape(nf, -1).T
    return out


def test_convolver_matches_naive(rng):
    X = rng.normal(size=(2, 8, 8, 3)).astype(np.float32)
    F = rng.normal(size=(4, 3, 3, 3)).astype(np.float32)
    out = np.asarray(Convolver(F)(X))
    np.testing.assert_allclose(out, _naive_conv(X, F), rtol=1e-4, atol=1e-4)


def test_convolver_with_whitener_matches_explicit(rng):
    X = rng.normal(size=(2, 8, 8, 3)).astype(np.float32)
    patches = rng.normal(size=(500, 27)).astype(np.float32)
    whitener = ZCAWhitenerEstimator(eps=0.1).fit(patches)
    F = rng.normal(size=(4, 3, 3, 3)).astype(np.float32)
    out = np.asarray(Convolver(F, whitener=whitener)(X))
    # Explicit: whiten each patch, then dot with raw filters.
    M = np.asarray(whitener.whitener)
    mu = np.asarray(whitener.mean)
    n, h, w, c = X.shape
    flat_f = F.reshape(4, -1)
    expected = np.zeros((n, 6, 6, 4))
    for i in range(6):
        for j in range(6):
            patch = X[:, i : i + 3, j : j + 3, :].reshape(n, -1)
            expected[:, i, j, :] = ((patch - mu) @ M) @ flat_f.T
    np.testing.assert_allclose(out, expected, rtol=1e-3, atol=1e-3)


def test_symmetric_rectifier():
    X = np.array([[[[1.0, -2.0]]]], dtype=np.float32)
    out = np.asarray(SymmetricRectifier(alpha=0.5)(X))
    np.testing.assert_allclose(out[0, 0, 0], [0.5, 0.0, 0.0, 1.5])


def test_pooler_modes(rng):
    X = rng.normal(size=(1, 4, 4, 2)).astype(np.float32)
    s = np.asarray(Pooler(2, 2, "sum")(X))
    m = np.asarray(Pooler(2, 2, "mean")(X))
    mx = np.asarray(Pooler(2, 2, "max")(X))
    block = X[0, :2, :2, 0]
    np.testing.assert_allclose(s[0, 0, 0, 0], block.sum(), rtol=1e-5)
    np.testing.assert_allclose(m[0, 0, 0, 0], block.mean(), rtol=1e-5)
    np.testing.assert_allclose(mx[0, 0, 0, 0], block.max(), rtol=1e-5)
    assert s.shape == (1, 2, 2, 2)


def test_random_patcher_shapes_and_determinism(rng):
    X = rng.normal(size=(4, 16, 16, 3)).astype(np.float32)
    a = np.asarray(RandomPatcher(32, 5, seed=7)(X))
    b = np.asarray(RandomPatcher(32, 5, seed=7)(X))
    assert a.shape == (32, 5, 5, 3)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("n,h,w,c,size", [
    (4, 10, 10, 3, 3),   # colour, square
    (3, 7, 11, 1, 4),    # one channel, not square
    (2, 5, 6, 3, 1),     # one pixel a patch
    (2, 6, 9, 3, 6),     # as tall as the image: every top is 0
])
def test_take_patches_equals_slices(n, h, w, c, size):
    """The patch cut (a gather of whole rows, then a select on the values'
    bits) gives each patch's values exactly, signed zero and NaN too, at
    the last top and left and for indices drawn twice."""
    from keystone_tpu.nodes.images.patches import _take_patches

    rng = np.random.default_rng(n * h * w * c * size)
    X = rng.normal(size=(n, h, w, c)).astype(np.float32)
    X[0, 0, 0, 0], X[-1, -1, -1, -1] = -0.0, np.nan
    image = rng.integers(0, n, size=40)
    top = rng.integers(0, h - size + 1, size=40)
    left = rng.integers(0, w - size + 1, size=40)
    image[:3], top[:3], left[:3] = n - 1, h - size, w - size
    image[3:5], top[3:5], left[3:5] = 0, 0, 0
    out = np.asarray(_take_patches(
        X, *(a.astype(np.int32) for a in (image, top, left)), size=size))
    ref = np.stack([X[i, t:t + size, l:l + size, :]
                    for i, t, l in zip(image, top, left)])
    assert out.shape == (40, size, size, c)
    assert np.array_equal(out.view(np.int32), ref.view(np.int32))


def test_windower_matches_direct(rng):
    X = rng.normal(size=(2, 6, 6, 1)).astype(np.float32)
    wins = np.asarray(Windower(2, 3)(X))
    assert wins.shape == (2 * 2 * 2, 3, 3, 1)
    np.testing.assert_allclose(wins[0], X[0, :3, :3, :], atol=1e-6)
    # second window of first image: rows 0-2, cols 2-4
    np.testing.assert_allclose(wins[1], X[0, :3, 2:5, :], atol=1e-6)


def test_center_corner_patcher(rng):
    X = rng.normal(size=(2, 8, 8, 3)).astype(np.float32)
    node = CenterCornerPatcher(crop_size=4, with_flips=True)
    out = np.asarray(node(X))
    assert out.shape == (2 * 10, 4, 4, 3)
    np.testing.assert_allclose(out[0], X[0, :4, :4, :], atol=1e-6)
    # flipped top-left crop of image 0 is view index 5 (width axis reversed)
    np.testing.assert_allclose(out[5], X[0, :4, :4, :][:, ::-1, :], atol=1e-6)


def test_pixel_nodes(rng):
    X = (rng.uniform(0, 255, size=(2, 4, 4, 3))).astype(np.float32)
    scaled = np.asarray(PixelScaler()(X))
    assert scaled.max() <= 1.0
    g = np.asarray(GrayScaler()(X))
    assert g.shape == (2, 4, 4, 1)
    v = np.asarray(ImageVectorizer()(X))
    assert v.shape == (2, 48)
    assert metadata_of(X).num_pixels == 48
