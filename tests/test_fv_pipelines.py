"""VOC and ImageNet pipeline integration tests + LCS/evaluator units."""

import numpy as np
import pytest

from keystone_tpu import native
from keystone_tpu.evaluation.augmented import AugmentedExamplesEvaluator
from keystone_tpu.evaluation.mean_average_precision import (
    MeanAveragePrecisionEvaluator,
)
from keystone_tpu.nodes.images.lcs import LCSExtractor

needs_native = pytest.mark.skipif(
    not native.available(), reason="native lib unavailable"
)


def test_lcs_shapes_and_stats(rng):
    X = rng.uniform(size=(2, 24, 24, 3)).astype(np.float32)
    node = LCSExtractor(step=4, bin_size=4)
    out = np.asarray(node(X))
    assert out.shape == (2, node.num_keypoints(24, 24), 96)
    # First keypoint, first cell stats == direct computation over the cell.
    cell = X[0, :4, :4, :]
    np.testing.assert_allclose(out[0, 0, :3], cell.mean(axis=(0, 1)), atol=1e-5)
    np.testing.assert_allclose(
        out[0, 0, 3:6], cell.std(axis=(0, 1)), atol=1e-3
    )


def test_map_evaluator_perfect_and_random():
    scores = np.array([[0.9, 0.1], [0.8, 0.2], [0.1, 0.9]])
    labels = np.array([[1, 0], [1, 0], [0, 1]])
    ev = MeanAveragePrecisionEvaluator(2)
    out = ev.evaluate(scores, labels)
    assert out["map"] > 0.99
    # Exact-AP variant too.
    assert MeanAveragePrecisionEvaluator(2, eleven_point=False).evaluate(
        scores, labels
    )["map"] == pytest.approx(1.0)


def test_map_evaluator_empty_class_is_nan():
    ev = MeanAveragePrecisionEvaluator(2)
    out = ev.evaluate(np.array([[0.5, 0.5]]), np.array([[1, 0]]))
    assert np.isnan(out["per_class_ap"][1])
    assert out["map"] == pytest.approx(out["per_class_ap"][0])


def test_augmented_evaluator():
    # 2 images x 2 views, 3 classes
    scores = np.array(
        [[1.0, 0, 0], [0.8, 0.2, 0], [0, 0, 1.0], [0, 0.4, 0.6]]
    )
    ev = AugmentedExamplesEvaluator(num_views=2)
    avg = ev.average_scores(scores)
    np.testing.assert_allclose(avg[0], [0.9, 0.1, 0.0])
    assert ev.top_k_error(scores, [0, 2], k=1) == 0.0
    with pytest.raises(ValueError, match="divisible"):
        ev.average_scores(scores[:3])


def test_voc_sift_fisher_end_to_end():
    from keystone_tpu.pipelines.images.voc_sift_fisher import (
        VOCSIFTFisherConfig,
        run,
    )

    out = run(
        VOCSIFTFisherConfig(
            synthetic_n=96,
            synthetic_classes=4,
            pca_dims=24,
            gmm_k=4,
            descriptor_sample=20_000,
            num_iters=1,
        )
    )
    # Multi-label textures are separable; mAP must beat the ~0.4 chance
    # level of this synthetic set decisively.
    assert out["map"] > 0.7, out["summary"]


def test_imagenet_sift_lcs_fv_end_to_end():
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
        run,
    )

    out = run(
        ImageNetSiftLcsFVConfig(
            synthetic_n=256,
            synthetic_classes=8,
            pca_dims=16,
            gmm_k=4,
            descriptor_sample=30_000,
            num_iters=1,
            top_k=5,
        )
    )
    assert out["top_k_error"] < 0.1, out["summary"]
    assert out["top_1_error"] < 0.5, out["summary"]


def test_fisher_branch_fit_served_from_disk(tmp_path, monkeypatch):
    """A second fit of the same FV branch (same images + params) comes from
    the content-addressed store — no SIFT pass, no GMM EM."""
    import numpy as np

    from keystone_tpu.nodes.images import GrayScaler
    from keystone_tpu.nodes.images.external import SIFTExtractor
    from keystone_tpu.nodes.images.external.fisher_vector import (
        GMMFisherVectorEstimator,
        fit_fisher_featurizer,
    )
    from keystone_tpu.workflow import PipelineEnv

    monkeypatch.setenv("KEYSTONE_CACHE_DIR", str(tmp_path))
    calls = {"n": 0}
    orig = GMMFisherVectorEstimator.fit

    def counting_fit(self, *a, **k):
        calls["n"] += 1
        return orig(self, *a, **k)

    monkeypatch.setattr(GMMFisherVectorEstimator, "fit", counting_fit)

    rng = np.random.default_rng(0)
    images = rng.uniform(size=(12, 32, 32, 3)).astype(np.float32)
    front = GrayScaler().and_then(SIFTExtractor(step=8, bin_size=4))

    def build():
        return fit_fisher_featurizer(
            front, images.copy(), pca_dims=8, gmm_k=3, em_iters=3,
            sample_size=2000,
        )

    PipelineEnv.reset()
    b1 = build()
    ref = np.asarray(b1(images[:4]).get())
    assert calls["n"] == 1

    PipelineEnv.reset()  # fresh session state, same disk store
    b2 = build()
    assert calls["n"] == 1  # served from disk: EM never ran again
    np.testing.assert_allclose(np.asarray(b2(images[:4]).get()), ref)


def test_imagenet_streamed_matches_eager():
    """Out-of-core mode: streaming batches through the featurizer and the
    host-streamed solver must reproduce the eager run (same fitting sample,
    same data — only the execution schedule differs)."""
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
        run,
    )

    base = dict(
        synthetic_n=192,
        synthetic_classes=6,
        pca_dims=16,
        gmm_k=4,
        descriptor_sample=20_000,
        num_iters=1,
        top_k=3,
    )
    eager = run(ImageNetSiftLcsFVConfig(**base))
    streamed = run(
        ImageNetSiftLcsFVConfig(
            **base, stream=True, stream_batch=64, fit_sample_images=192
        )
    )
    # Same featurizer (full train as fitting sample), same solve — the
    # schedules agree to solver tolerance.
    assert abs(streamed["top_k_error"] - eager["top_k_error"]) < 0.05
    assert abs(streamed["top_1_error"] - eager["top_1_error"]) < 0.1


@needs_native
def test_fitted_native_pipeline_save_load(tmp_path):
    import numpy as np

    from keystone_tpu.loaders.voc import VOCLoader
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.pipelines.images.voc_sift_fisher import (
        VOCSIFTFisherConfig,
        build_featurizer,
    )
    from keystone_tpu.workflow import load_pipeline, save_pipeline

    train, test = VOCLoader.synthetic(n=48, num_classes=4)
    conf = VOCSIFTFisherConfig(
        pca_dims=16, gmm_k=4, descriptor_sample=10000, sift_backend="native"
    )
    feat = build_featurizer(conf, train.data)
    targets = (2.0 * train.labels - 1.0).astype(np.float32)
    p = feat.and_then(
        BlockLeastSquaresEstimator(block_size=128, num_iters=1, lam=1e-3),
        train.data,
        targets,
    ).fit()
    path = str(tmp_path / "voc.pkl")
    save_pipeline(p, path)
    lp = load_pipeline(path)
    np.testing.assert_array_equal(
        np.asarray(p(test.data).get()), np.asarray(lp(test.data).get())
    )


def test_imagenet_with_test_time_augmentation():
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
        run,
    )

    out = run(
        ImageNetSiftLcsFVConfig(
            synthetic_n=160,
            synthetic_classes=6,
            pca_dims=16,
            gmm_k=4,
            descriptor_sample=20_000,
            num_iters=1,
            augment=True,
        )
    )
    # top-1 carries the signal: 6-class chance is 0.83 top-1 error; the
    # top-5 floor (1/6) is too close to the threshold to be meaningful.
    assert out["top_1_error"] < 0.3, out["summary"]
    assert out["top_k_error"] < 0.1, out["summary"]


def test_imagenet_resolve_scale_defaults():
    """Real data defaults to the reference's 64k-dim headline config
    (gmm_k=256, 3 epochs — BASELINE.json); synthetic stays CI-scale; an
    explicit value always wins (VERDICT r3 missing #4)."""
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
        resolve_scale,
    )

    real = resolve_scale(ImageNetSiftLcsFVConfig(data_path="/d"))
    assert (real.gmm_k, real.num_iters) == (256, 3)
    assert 2 * (2 * real.gmm_k * real.pca_dims) == 65_536
    synth = resolve_scale(ImageNetSiftLcsFVConfig())
    assert (synth.gmm_k, synth.num_iters) == (16, 2)
    explicit = resolve_scale(
        ImageNetSiftLcsFVConfig(data_path="/d", gmm_k=32, num_iters=1)
    )
    assert (explicit.gmm_k, explicit.num_iters) == (32, 1)
