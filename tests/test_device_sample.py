"""The descriptor sample is drawn where the descriptors are: indices on the
host, rows gathered on the device, and the sample stays there through the
PCA and the mixture. The oracles here are the host path as it was before
the gather moved, written out with numpy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.nodes.images import GrayScaler
from keystone_tpu.nodes.images.external import (
    GMMFisherVectorEstimator,
    SIFTExtractor,
)
from keystone_tpu.nodes.images.external import fisher_vector
from keystone_tpu.nodes.images.external.fisher_vector import fit_fisher_featurizer
from keystone_tpu.nodes.images.lcs import LCSExtractor
from keystone_tpu.nodes.learning import PCAEstimator
from keystone_tpu.nodes.learning.gmm import GaussianMixtureModelEstimator
from keystone_tpu.nodes.learning.pca import PCATransformer
from keystone_tpu.nodes.stats import samplers
from keystone_tpu.nodes.stats.samplers import sample_rows
from keystone_tpu.config import config
from keystone_tpu.utils.metrics import (
    CompileEventCounter,
    active_tracer,
    reset_tracer,
)

N, M = 12, 7  # descriptor sets, descriptors a set

_compile_events = CompileEventCounter()  # one listener a process


@pytest.fixture
def tracer():
    """The process's tracer, armed for the test and dropped after it."""
    prior = config.trace
    config.trace = True
    reset_tracer()
    try:
        yield active_tracer()
    finally:
        config.trace = prior
        reset_tracer()


def _host_sample(rows: np.ndarray, size: int, seed: int) -> np.ndarray:
    """The draw as the host path made it: numpy's choice, sort, gather."""
    n = rows.shape[0]
    if size >= n:
        return rows
    idx = np.random.default_rng(seed).choice(n, size=size, replace=False)
    return rows[np.sort(idx)]


@pytest.mark.parametrize("d", [128, 96])
@pytest.mark.parametrize("size", [30, N * M, N * M + 5], ids=["below", "at", "above"])
def test_device_sample_is_the_host_sample_bit_for_bit(rng, d, size):
    host = rng.normal(size=(N, M, d)).astype(np.float32)
    descs = jnp.asarray(host)
    want = _host_sample(host.reshape(-1, d), size, seed=3)
    assert want.shape == (min(size, N * M), d)
    got = sample_rows(descs, size, seed=3)
    assert isinstance(got, jax.Array)  # gathered on the device, left there
    np.testing.assert_array_equal(np.asarray(got), want)
    # One definition of the draw: the host array's sample is the same rows.
    again = sample_rows(host.reshape(-1, d), size, seed=3)
    assert isinstance(again, np.ndarray)
    np.testing.assert_array_equal(again, want)
    np.testing.assert_array_equal(sample_rows(host, size, seed=3), want)


def _fronts():
    return {
        "sift": GrayScaler().and_then(
            SIFTExtractor(step=8, bin_size=4, backend="xla")),
        "lcs": LCSExtractor(step=8, bin_size=4).to_pipeline(),
    }


def _fitted(branch):
    """(PCA, Fisher vector) transformers of a fitted branch."""
    stages = []
    for t in branch.transformers():
        stages.extend(getattr(t, "stages", [t]))
    (pca,) = [s for s in stages if isinstance(s, PCATransformer)]
    (fv,) = [s for s in stages if isinstance(s, fisher_vector.FisherVector)]
    return pca, fv


@pytest.mark.parametrize("front_name", ["sift", "lcs"])
def test_branch_fit_on_the_device_is_the_host_path_bit_for_bit(rng, front_name):
    front = _fronts()[front_name]
    images = rng.uniform(size=(10, 32, 32, 3)).astype(np.float32)
    dims, k, iters, size, seed = 8, 3, 4, 60, 5
    branch = fit_fisher_featurizer(
        front, images, pca_dims=dims, gmm_k=k, em_iters=iters,
        sample_size=size, seed=seed,
    )
    pca, fv = _fitted(branch)

    # The host path: everything fetched, flattened and sampled with numpy,
    # uploaded for the PCA, the projection fetched again for the mixture.
    described = front(images).get()
    assert isinstance(described, jax.Array)  # the path under test is the device's
    descs = np.asarray(described)
    flat = _host_sample(descs.reshape(-1, descs.shape[-1]), size, seed)
    assert flat.shape[0] == size < descs.shape[0] * descs.shape[1]
    want_pca = PCAEstimator(dims=dims).fit(flat)
    projected = np.asarray(want_pca(flat)).astype(np.float32, copy=False)
    want = GaussianMixtureModelEstimator(k=k, max_iters=iters, seed=seed).fit(projected)

    np.testing.assert_array_equal(np.asarray(pca.components), np.asarray(want_pca.components))
    np.testing.assert_array_equal(np.asarray(pca.mean), np.asarray(want_pca.mean))
    np.testing.assert_array_equal(fv.weights, np.asarray(want.weights))
    np.testing.assert_array_equal(fv.means, np.asarray(want.means))
    np.testing.assert_array_equal(fv.variances, np.asarray(want.variances))


@pytest.mark.parametrize("where", ["device", "host"])
def test_fisher_estimator_leaves_a_device_array_on_the_device(
        rng, monkeypatch, tracer, where):
    host = rng.normal(size=(N, M, 6)).astype(np.float32)
    seen = []
    orig = GaussianMixtureModelEstimator.fit

    def spy(self, data):
        seen.append(data)
        return orig(self, data)

    monkeypatch.setattr(GaussianMixtureModelEstimator, "fit", spy)
    estimator = GMMFisherVectorEstimator(k=2, em_iters=3, sample_size=40, seed=1)
    fv = estimator.fit(jnp.asarray(host) if where == "device" else host)
    (sample,) = seen
    assert isinstance(sample, jax.Array if where == "device" else np.ndarray)
    np.testing.assert_array_equal(
        np.asarray(sample), _host_sample(host.reshape(-1, 6), 40, seed=1))
    names = [s["name"] for s in tracer.spans()]
    assert "fisher.fetch" not in names
    # The host's flatten is a view with no span of its own: ``on_device``
    # on ``fisher.sample`` says where the descriptors lay.
    assert "fisher.flatten" not in names
    (drawn,) = [s for s in tracer.spans() if s["name"] == "fisher.sample"]
    assert drawn["args"]["on_device"] == (where == "device")
    assert drawn["args"]["rows_in"] == N * M and drawn["args"]["rows_out"] == 40
    assert drawn["args"]["bytes"] == 40 * 6 * 4
    # Either way the same mixture.
    other = GMMFisherVectorEstimator(k=2, em_iters=3, sample_size=40, seed=1).fit(
        host if where == "device" else jnp.asarray(host))
    np.testing.assert_array_equal(fv.means, other.means)


def test_a_second_branch_fit_builds_no_new_gather_program(rng):
    """The indices are an argument of the gather, so a new seed (a new
    draw) at the same shapes is the same program: the second and third fits
    make the same compile requests, fewer than the first, and none of them
    is the gather's."""
    front = _fronts()["lcs"]
    images = rng.uniform(size=(10, 32, 32, 3)).astype(np.float32)

    def fit(seed):
        before = _compile_events.count
        fit_fisher_featurizer(front, images, pca_dims=8, gmm_k=3, em_iters=2,
                              sample_size=60, seed=seed)
        return _compile_events.count - before

    first = fit(0)
    programs = samplers._take_rows._cache_size()
    assert programs >= 1
    second, third = fit(1), fit(2)
    assert samplers._take_rows._cache_size() == programs
    assert second == third < first
