"""Every canonical pipeline family fits, saves, loads, and reproduces its
predictions bit-for-bit — the model-export contract (SURVEY.md §5
checkpoint/resume row [unverified]). This net catches any node that sneaks
unpicklable state (lambdas, closures, file handles) into a fitted graph,
the class of bug that broke text-pipeline export until round 2.
"""

import numpy as np
import pytest

from keystone_tpu.workflow.serialization import load_pipeline, save_pipeline


def _roundtrip(pipe, sample, tmp_path, tag):
    ref = np.asarray(pipe.apply(sample).get())
    path = str(tmp_path / f"{tag}.pkl")
    save_pipeline(pipe, path)
    got = np.asarray(load_pipeline(path).apply(sample).get())
    np.testing.assert_array_equal(got, ref)


def test_mnist_fft_roundtrip(tmp_path):
    from keystone_tpu.loaders import MnistLoader
    from keystone_tpu.pipelines.images.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_pipeline,
    )

    train, _ = MnistLoader.synthetic(n=256, seed=0)
    conf = MnistRandomFFTConfig(num_ffts=2, synthetic_n=256)
    pipe = build_pipeline(conf, train.data, train.labels).fit()
    _roundtrip(pipe, train.data[:16], tmp_path, "mnist")


def test_cifar_conv_roundtrip(tmp_path):
    from keystone_tpu.loaders.cifar import CifarLoader
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
    from keystone_tpu.pipelines.images.random_patch_cifar import (
        RandomPatchCifarConfig,
        build_featurizer,
    )

    train, _ = CifarLoader.synthetic(n=192)
    conf = RandomPatchCifarConfig(
        num_filters=16, patch_sample=256, synthetic_n=192, num_iters=1
    )
    feat = build_featurizer(conf, train.data)
    targets = ClassLabelIndicators(10)(train.labels)
    pipe = (
        feat.and_then(
            BlockLeastSquaresEstimator(num_iters=1, lam=1.0),
            train.data,
            targets,
        )
        .and_then(MaxClassifier())
        .fit()
    )
    _roundtrip(pipe, train.data[:8], tmp_path, "cifar")


def test_timit_features_roundtrip(tmp_path):
    from keystone_tpu.loaders.timit import TimitFeaturesDataLoader
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.stats import CosineRandomFeatures
    from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier

    train, _ = TimitFeaturesDataLoader.synthetic(n=256)
    targets = ClassLabelIndicators(int(train.labels.max()) + 1)(train.labels)
    pipe = (
        CosineRandomFeatures.create(
            train.data.shape[1], 512, gamma=0.05, seed=0
        )
        .and_then(
            BlockLeastSquaresEstimator(num_iters=1, lam=1e-2),
            train.data,
            targets,
        )
        .and_then(MaxClassifier())
        .fit()
    )
    _roundtrip(pipe, train.data[:16], tmp_path, "timit")


def test_newsgroups_nb_roundtrip(tmp_path):
    from keystone_tpu.loaders.newsgroups import NewsgroupsDataLoader
    from keystone_tpu.nodes.learning import NaiveBayesEstimator
    from keystone_tpu.nodes.nlp import (
        CommonSparseFeatures,
        LowerCase,
        NGramsFeaturizer,
        TermFrequency,
        Tokenizer,
        Trim,
    )
    from keystone_tpu.nodes.util import MaxClassifier

    train, _test, classes = NewsgroupsDataLoader.synthetic(n=200)
    pipe = (
        Trim()
        .and_then(LowerCase())
        .and_then(Tokenizer())
        .and_then(NGramsFeaturizer(1, 2))
        .and_then(TermFrequency("log"))
        .and_then(CommonSparseFeatures(2000), train.data)
        .and_then(NaiveBayesEstimator(len(classes)), train.data, train.labels)
        .and_then(MaxClassifier())
        .fit()
    )
    _roundtrip(pipe, train.data[:16], tmp_path, "newsgroups")


def test_sparse_csr_text_roundtrip(tmp_path):
    """The explicit-CSR text path (sparse=True vectorizer + NB)."""
    from keystone_tpu.nodes.learning import NaiveBayesEstimator
    from keystone_tpu.nodes.nlp import (
        CommonSparseFeatures,
        TermFrequency,
        Tokenizer,
    )

    rng = np.random.default_rng(0)
    texts, labels = [], []
    for _ in range(120):
        c = int(rng.integers(0, 3))
        texts.append(
            " ".join(f"s{c}x{int(rng.integers(0, 20))}" for _ in range(10))
        )
        labels.append(c)
    labels = np.asarray(labels, dtype=np.int32)
    pipe = (
        Tokenizer()
        .and_then(TermFrequency("log"))
        .and_then(CommonSparseFeatures(1000, sparse=True), texts)
        .and_then(NaiveBayesEstimator(3), texts, labels)
        .fit()
    )
    _roundtrip(pipe, texts[:16], tmp_path, "sparse_csr")


def test_kernel_pcg_model_roundtrip(tmp_path):
    from keystone_tpu.nodes.learning import KernelRidgeCG

    rng = np.random.default_rng(0)
    X = rng.normal(size=(128, 8)).astype(np.float32)
    Y = rng.normal(size=(128, 2)).astype(np.float32)
    pipe = (
        KernelRidgeCG(
            gamma=0.2, lam=1e-2, max_iters=100, precond_landmarks=32
        )
        .with_data(X, Y)
        .fit()
    )
    _roundtrip(pipe, X[:16], tmp_path, "krr_pcg")


def test_text_estimator_prefix_is_persistable():
    """The whole canonical text prefix — corpus fingerprint + stable nlp
    node signatures — must produce a non-None structural digest, or the
    cross-process fit cache can never serve text pipelines."""
    from keystone_tpu.nodes.learning import NaiveBayesEstimator
    from keystone_tpu.nodes.nlp import (
        CommonSparseFeatures,
        LowerCase,
        NGramsFeaturizer,
        TermFrequency,
        Tokenizer,
        Trim,
    )
    from keystone_tpu.workflow.graph import structural_digest
    from keystone_tpu.workflow.operators import EstimatorOperator

    texts = [f"doc number {i} words" for i in range(50)]
    labels = np.arange(50, dtype=np.int32) % 3
    p = (
        Trim()
        .and_then(LowerCase())
        .and_then(Tokenizer())
        .and_then(NGramsFeaturizer(1, 2))
        .and_then(TermFrequency("log"))
        .and_then(CommonSparseFeatures(500), texts)
        .and_then(NaiveBayesEstimator(3), texts, labels)
    )
    g = p.graph
    est_nodes = [
        nid
        for nid in g.reachable([p.sink])
        if isinstance(g.operators[nid], EstimatorOperator)
    ]
    assert est_nodes
    for nid in est_nodes:
        assert structural_digest(g, nid) is not None


def test_logistic_roundtrip(tmp_path):
    from keystone_tpu.nodes.learning import LogisticRegressionEstimator
    from keystone_tpu.nodes.stats import StandardScaler

    rng = np.random.default_rng(0)
    X = rng.normal(size=(128, 16)).astype(np.float32)
    y = rng.integers(0, 3, size=128)
    pipe = (
        StandardScaler()
        .with_data(X)
        .and_then(LogisticRegressionEstimator(3, max_iters=20), X, y)
        .fit()
    )
    _roundtrip(pipe, X[:16], tmp_path, "logistic")
