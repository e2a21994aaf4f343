"""A host batch that several walks of one fit read reaches the device once
(``workflow.operators.placed_batch``): one upload, one fingerprint, the
host array's signature, nothing left on the device when the fit is over.
On one device, as the benchmark's cells run; the fake mesh's cases are in
``tests/test_sharded_ingest.py``."""

import gc

import jax
import numpy as np
import pytest

from keystone_tpu.config import config
from keystone_tpu.loaders.imagenet import ImageNetLoader
from keystone_tpu.nodes.learning import BlockWeightedLeastSquaresEstimator
from keystone_tpu.nodes.util import ClassLabelIndicators
from keystone_tpu.pipelines.images import imagenet_sift_lcs_fv as imagenet
from keystone_tpu.utils.mesh import default_mesh, set_default_mesh
from keystone_tpu.utils.metrics import (
    program_counters,
    recorded_tracer,
    reset_tracer,
)
from keystone_tpu.workflow import PipelineEnv, Transformer, placed_batch
from keystone_tpu.workflow.fingerprint import (
    PLACED,
    array_fingerprint,
    batch_fingerprint,
)
from keystone_tpu.workflow.operators import DatasetOperator

CLASSES = 4
MIB = 1 << 20


@pytest.fixture(autouse=True)
def one_device():
    # conftest's fresh_env drops the narrow mesh again after the test.
    set_default_mesh(default_mesh(devices=jax.devices()[:1]))


@pytest.fixture(scope="module")
def train():
    train, _test = ImageNetLoader.synthetic(n=96, num_classes=CLASSES)
    assert isinstance(train.data, np.ndarray) and train.data.nbytes > MIB
    return train


def _conf():
    return imagenet.resolve_scale(imagenet.ImageNetSiftLcsFVConfig(
        synthetic_n=96, synthetic_classes=CLASSES, pca_dims=8, gmm_k=3,
        gmm_iters=3, descriptor_sample=5000, num_iters=1))


def _weights(scored):
    from keystone_tpu.nodes.learning.block_least_squares import BlockLinearMapper

    (mapper,) = [
        op.transformer for op in scored.graph.operators.values()
        if isinstance(getattr(op, "transformer", None), BlockLinearMapper)
    ]
    return [np.asarray(w) for w in mapper.W_blocks]


def _fit_from_the_host_array(conf, train):
    """The fit with every walk fed the host array itself: what
    ``imagenet.fit`` did before it placed the batch."""
    featurizer = imagenet.build_featurizer(conf, train.data)
    targets = ClassLabelIndicators(CLASSES)(train.labels)
    solver = BlockWeightedLeastSquaresEstimator(
        block_size=conf.block_size, num_iters=conf.num_iters, lam=conf.lam,
        mixture_weight=conf.mixture_weight)
    return featurizer.and_then(solver, train.data, targets).fit()


@pytest.fixture
def traced_fit(train, monkeypatch):
    """One traced ``imagenet.fit`` from the host array: (the fitted
    pipeline, the fit's spans, what every ``batch_call`` was handed)."""
    handed = []
    batch_call = Transformer.batch_call

    def watching(self, X):
        handed.append((type(X), int(getattr(X, "nbytes", 0))))
        return batch_call(self, X)

    monkeypatch.setattr(Transformer, "batch_call", watching)
    monkeypatch.setattr(config, "trace", True)
    reset_tracer()
    try:
        _featurizer, scored = imagenet.fit(_conf(), train, CLASSES)
        spans = recorded_tracer().spans()
    finally:
        reset_tracer()
    return scored, spans, handed


def test_the_walks_read_a_device_array_and_one_span_counts_the_upload(train, traced_fit):
    _scored, spans, handed = traced_fit
    assert not [h for h in handed if h[0] is np.ndarray and h[1] > MIB]
    # The four walks: each branch's descriptors, then each branch's chain.
    assert len([h for h in handed if h[1] == train.data.nbytes]) == 4
    (root,) = [s for s in spans if s["name"] == "fit" and s.get("parent_id") is None]
    (place,) = [s for s in spans if s["name"] == "data.place"]
    assert place["parent_id"] == root["id"] and place["cat"] == "pipeline"
    assert place["args"]["bytes"] == train.data.nbytes
    assert place["args"]["rows"] == len(train.data)


def test_a_fit_fingerprints_its_images_once(traced_fit):
    _scored, spans, _handed = traced_fit
    (root,) = [s for s in spans if s["name"] == "fit" and s.get("parent_id") is None]
    assert root["args"]["dataset_fingerprints"] == 1
    assert root["args"]["closure_program_calls"] == 0


def test_the_label_indicators_do_not_come_back_to_be_hashed(train):
    # (rows, classes) indicators of 1.5 MiB: a dataset of the fit's graph
    # would be fetched and hashed whole; as a node over the labels it is
    # neither, and the images are the one dataset fingerprinted.
    classes = 4096
    assert len(train.data) * classes * 4 > MIB
    calls = program_counters.calls()
    imagenet.fit(_conf(), train, classes)
    assert program_counters.since(calls)["dataset_fingerprints"] == 1


def test_weights_are_those_of_a_fit_from_the_host_array(train):
    _featurizer, scored = imagenet.fit(_conf(), train, CLASSES)
    got = _weights(scored)
    PipelineEnv.reset()
    calls = program_counters.calls()
    want = _weights(_fit_from_the_host_array(_conf(), train))
    # The path it is compared with hashes the images for three walks.
    assert program_counters.since(calls)["dataset_fingerprints"] == 3
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_nothing_of_the_images_shape_outlives_the_fit(train):
    def live():
        return [a for a in jax.live_arrays() if a.shape == train.data.shape]

    gc.collect()
    assert not live()
    gc.disable()  # a cycle may not be what frees 0.4 GB of a chip
    try:
        fitted = imagenet.fit(_conf(), train, CLASSES)
        assert not live()
    finally:
        gc.enable()
    assert not PLACED
    del fitted


@pytest.mark.parametrize("limit", [64 * MIB, MIB // 4], ids=["whole", "sampled"])
def test_the_placed_batch_signs_as_its_host_array(train, monkeypatch, limit):
    monkeypatch.setattr(config, "fingerprint_max_bytes", limit)
    host = train.data
    want = DatasetOperator(host).signature()
    assert want[1][0] == ("ndarray" if host.nbytes <= limit else "ndarray-sampled")
    calls = program_counters.calls()
    with placed_batch(host) as placed:
        assert isinstance(placed, jax.Array) and not placed.is_deleted()
        assert placed.sharding.device_set == {jax.devices()[0]}
        assert DatasetOperator(placed).signature() == want
        assert DatasetOperator(placed).signature() == want  # a second walk
        assert batch_fingerprint(placed) == want[1] == array_fingerprint(host)
        np.testing.assert_array_equal(np.asarray(placed), host)
    # Hashed in the placing and by the ``array_fingerprint(host)`` above.
    assert program_counters.since(calls)["dataset_fingerprints"] == 2
    assert not PLACED


class _RowsMinor:
    """A device array whose host copy comes back with its rows minor, as a
    TPU hands back a batch of images."""

    def __init__(self, x):
        self.x, self.shape = x, x.shape

    def __array__(self, dtype=None, copy=None):
        return np.asfortranarray(np.asarray(self.x))

    def reshape(self, *shape):
        return self.x.reshape(*shape)


@pytest.mark.parametrize("shape", [(7,), (5, 3), (6, 4, 4, 3)])
def test_a_device_array_signs_as_its_host_array(shape):
    """A device array's values reach the hash in C order
    (``fingerprint.host_values``: one whose host copy is not is flattened on
    the device and fetched again); the bytes, and so every signature over
    them, are the host array's."""
    from keystone_tpu.workflow.fingerprint import host_values, stable_value

    host = np.arange(np.prod(shape), dtype=np.float32).reshape(shape) - 3.5
    on_device = jax.device_put(host)
    for got in (host_values(on_device), host_values(_RowsMinor(on_device))):
        assert got.flags.c_contiguous and got.shape == shape
        np.testing.assert_array_equal(got, host)
    assert DatasetOperator(on_device).signature() == DatasetOperator(host).signature()
    assert batch_fingerprint(on_device) == array_fingerprint(host)
    assert stable_value(on_device) == stable_value(host)


def test_what_is_no_numeric_host_array_passes_through():
    on_device = jax.numpy.ones((4, 3))
    for data in (on_device, ["a", "b"], np.array(["a", "b"]), 3.0):
        with placed_batch(data) as got:
            assert got is data
    assert not PLACED


def test_a_second_fit_hits_the_branches_disk_entries(train, tmp_path, monkeypatch):
    from keystone_tpu.nodes.images.external.fisher_vector import (
        GMMFisherVectorEstimator,
    )

    monkeypatch.setenv("KEYSTONE_CACHE_DIR", str(tmp_path))
    fits = []
    fit = GMMFisherVectorEstimator.fit
    monkeypatch.setattr(GMMFisherVectorEstimator, "fit",
                        lambda self, *a: fits.append(1) or fit(self, *a))
    # Entries written under the host array's key ...
    PipelineEnv.reset()
    want = _weights(_fit_from_the_host_array(_conf(), train))
    assert len(fits) == 2
    # ... are the placed batch's: no mixture is fitted again, and the
    # images are hashed once, so none came back from the device to be.
    PipelineEnv.reset()
    calls = program_counters.calls()
    _featurizer, scored = imagenet.fit(_conf(), train, CLASSES)
    assert len(fits) == 2
    assert program_counters.since(calls)["dataset_fingerprints"] == 1
    for g, w in zip(_weights(scored), want):
        np.testing.assert_array_equal(g, w)
