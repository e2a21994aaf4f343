"""A transformer's program is found by its structure, never by its identity.

The rule (``Transformer.shares_program``): where every static field hashes
by value, arrays or none, the jitted program is the one every transformer
of that class, those fields and those shapes shares, so a second fit of a
process traces and compiles nothing. Tiny widths on the CPU; compile
requests are counted, never timed.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.config import config
from keystone_tpu.loaders.labeled_data import LabeledData
from keystone_tpu.nodes.images.external.fisher_vector import FisherVector, _fv_tpu
from keystone_tpu.nodes.learning.block_least_squares import BlockLinearMapper
from keystone_tpu.nodes.learning.gmm import GaussianMixtureModel
from keystone_tpu.nodes.learning.pca import PCATransformer
from keystone_tpu.nodes.util import ClassLabelIndicators
from keystone_tpu.pipelines.images import imagenet_sift_lcs_fv as imagenet
from keystone_tpu.pipelines.images import random_patch_cifar as cifar
from keystone_tpu.pipelines.speech import timit
from keystone_tpu.utils.metrics import (
    CompileEventCounter,
    program_counters,
    recorded_tracer,
    reset_tracer,
)
from keystone_tpu.workflow import Transformer
from keystone_tpu.workflow.pipeline import _Bound, _Closure, _program

# One compile oracle a process (registration is permanent).
COMPILES = CompileEventCounter()


def _rng(seed):
    return np.random.default_rng(seed)


def _normal(seed, *shape):
    return _rng(seed).normal(size=shape).astype(np.float32)


def _mixture(seed, k=3, d=4):
    r = _rng(seed)
    w = r.uniform(0.5, 1.5, size=k)
    return ((w / w.sum()).astype(np.float32), _normal(seed + 1, k, d),
            r.uniform(0.5, 2.0, size=(k, d)).astype(np.float32))


def _constants(text):
    """Element counts of the constants in a lowered module's text."""
    counts = []
    for shape in re.findall(r"stablehlo\.constant[^\n]*?: tensor<([^>]*)>", text):
        dims = [int(n) for n in shape.split("x")[:-1]]
        counts.append(int(np.prod(dims)) if dims else 1)
    return counts


# ---------------------------------------------------- the four fitted nodes


def _pca(seed):
    return PCATransformer(_normal(seed, 6, 3), _normal(seed + 1, 6))


def _pca_reference(node, X):
    return (X - np.asarray(node.mean)) @ np.asarray(node.components)


def _fisher(seed):
    return FisherVector(*_mixture(seed))


def _fisher_reference(node, X):
    """The encoding from its definition: responsibilities by a plain
    softmax of log densities, both gradient blocks, float64."""
    w, mu, var = (np.asarray(a, np.float64) for a in
                  (node.weights, node.means, node.variances))
    X = np.asarray(X, np.float64)
    m = X.shape[1]
    z = (X[:, :, None, :] - mu) / np.sqrt(var)  # (B, m, k, d)
    log_r = np.log(w) - 0.5 * np.log(var).sum(-1) - 0.5 * (z * z).sum(-1)
    r = np.exp(log_r - log_r.max(-1, keepdims=True))
    r /= r.sum(-1, keepdims=True)
    gmu = np.einsum("bmk,bmkd->bkd", r, z) / (m * np.sqrt(w))[:, None]
    gvar = np.einsum("bmk,bmkd->bkd", r, z * z - 1.0) / (m * np.sqrt(2 * w))[:, None]
    B = X.shape[0]
    return np.concatenate([gmu.reshape(B, -1), gvar.reshape(B, -1)], axis=-1)


def _mapper(seed):
    blocks = [(0, 4), (4, 6)]
    return BlockLinearMapper([_normal(seed + i, e - s, 3) for i, (s, e) in enumerate(blocks)],
                             blocks, _normal(seed + 7, 3))


def _mapper_reference(node, X):
    return X @ np.asarray(node.W) + np.asarray(node.b)


def _gmm(seed):
    return GaussianMixtureModel(*_mixture(seed, d=6))


def _gmm_reference(node, X):
    w, mu, var = (np.asarray(a, np.float64) for a in
                  (node.weights, node.means, node.variances))
    z = (np.asarray(X, np.float64)[:, None, :] - mu) / np.sqrt(var)
    log_r = np.log(w) - 0.5 * np.log(var).sum(-1) - 0.5 * (z * z).sum(-1)
    r = np.exp(log_r - log_r.max(-1, keepdims=True))
    return r / r.sum(-1, keepdims=True)


FITTED = {
    "pca": (_pca, _pca_reference, (16, 6), "jit_apply_PCATransformer", 3),
    "fisher": (_fisher, _fisher_reference, (5, 7, 4), "jit_apply_FisherVector", 4),
    "block_mapper": (_mapper, _mapper_reference, (16, 6),
                     "jit_apply_BlockLinearMapper", 4),
    "mixture": (_gmm, _gmm_reference, (16, 6), "jit_apply_GaussianMixtureModel", 4),
}


@pytest.mark.parametrize("kind", sorted(FITTED))
def test_other_values_of_equal_shapes_make_no_compile_request(kind):
    make, reference, shape, _module, _args = FITTED[kind]
    X = _normal(0, *shape)
    first, second = make(1), make(20)
    a = np.asarray(first.batch_call(X))
    before = COMPILES.count
    b = np.asarray(second.batch_call(X))
    assert COMPILES.count == before  # the first one's executable
    assert first._jitted().program is second._jitted().program
    assert not np.allclose(a, b)
    # Each its own values' answer.
    np.testing.assert_allclose(a, reference(first, X), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(b, reference(second, X), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kind", sorted(FITTED))
def test_the_fitted_arrays_are_arguments_and_no_constant(kind):
    make, _reference, shape, module, n_args = FITTED[kind]
    node = make(1)
    assert node.shares_program() and isinstance(node._jitted(), _Bound)
    text = node._jitted().lower(_normal(0, *shape)).as_text()
    assert module in text
    # The arrays and X; nothing beyond a scalar is written into the program.
    (main,) = re.findall(r"func\.func public @main\(([^\n]*)", text)
    assert len(re.findall(r"%arg\d+: tensor", main)) == n_args
    assert all(n <= 1 for n in _constants(text)), _constants(text)


def test_a_pca_without_a_mean_is_another_program_and_right():
    X = _normal(0, 16, 6)
    centred, plain = _pca(1), PCATransformer(_normal(1, 6, 3))
    assert jax.tree_util.tree_leaves(plain) == [plain.components]
    assert (jax.tree_util.tree_structure(plain)
            != jax.tree_util.tree_structure(centred))
    np.testing.assert_allclose(np.asarray(plain.batch_call(X)),
                               X @ np.asarray(plain.components), rtol=1e-5, atol=1e-6)
    before = COMPILES.count
    other = PCATransformer(_normal(30, 6, 3))
    np.testing.assert_allclose(np.asarray(other.batch_call(X)),
                               X @ np.asarray(other.components), rtol=1e-5, atol=1e-6)
    assert COMPILES.count == before


def test_the_pallas_backend_shares_a_program_and_agrees_with_the_einsums():
    X = _normal(0, 5, 7, 4)
    nodes = [FisherVector(*_mixture(seed), backend="pallas") for seed in (1, 20)]
    assert all(n.shares_program() and n.uses_pallas for n in nodes)
    got = np.asarray(nodes[0].batch_call(X))
    before = COMPILES.count
    again = np.asarray(nodes[1].batch_call(X))
    assert COMPILES.count == before
    for node, out in zip(nodes, (got, again)):
        want = _fv_tpu(jnp.asarray(X), *(jnp.asarray(a) for a in
                                         (node.weights, node.means, node.variances)))
        np.testing.assert_allclose(out, np.asarray(want), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(out, _fisher_reference(node, X), rtol=2e-4, atol=2e-5)
    # The einsum backend is another program: ``backend`` is static.
    assert (jax.tree_util.tree_structure(nodes[0])
            != jax.tree_util.tree_structure(_fisher(1)))


def test_the_native_backend_stays_on_the_host():
    node = FisherVector(*_mixture(1), backend="native")
    assert not node.jittable and isinstance(node.weights, np.ndarray)


# ----------------------------------------- what keeps a closure of its own


class _Mapped(Transformer):
    """A callable for a field: hashed by identity."""

    def __init__(self, fn):
        self.fn = fn

    def apply_batch(self, X):
        return self.fn(X)


class _Undeclared(Transformer):
    """An array it does not name: the static part does not hash."""

    def __init__(self, shift):
        self.shift = jnp.asarray(shift)

    def apply_batch(self, X):
        return X + self.shift


class _Handle:
    """The default ``__hash__``: identity."""


class _Holding(Transformer):
    def __init__(self, handle):
        self.handle = handle

    def apply_batch(self, X):
        return X * 2.0


@pytest.mark.parametrize("make", [
    lambda: _Mapped(jnp.tanh),
    lambda: _Mapped(lambda X: X + 1.0),
    lambda: _Undeclared(np.ones(6, np.float32)),
    lambda: _Holding(_Handle()),
    lambda: _Holding((1, [2])),
], ids=["function", "lambda", "undeclared_array", "identity_hashed_object",
        "unhashable_in_a_tuple"])
def test_what_does_not_hash_by_value_keeps_its_own_closure(make):
    X = _normal(0, 8, 6)
    node, twin = make(), make()
    assert not node.shares_program()
    name = type(node).__name__
    entries = _program(name)._cache_size()
    calls = program_counters.calls()
    out = np.asarray(node.batch_call(X))
    np.testing.assert_array_equal(np.asarray(node.apply_batch(X)), out)
    # Its own jitted closure, kept on it, under the name it always had;
    # nothing of it in the shared program's cache.
    assert isinstance(node._jitted(), _Closure)
    assert node._jitted() is node._jitted() is not twin._jitted()
    assert "jit_apply_batch" in node._jitted().lower(X).as_text()
    assert _program(name)._cache_size() == entries
    assert program_counters.since(calls) == {
        "shared_program_calls": 0, "closure_program_calls": 1,
        "dataset_fingerprints": 0, "collective_bytes": 0}


def test_value_hashed_fields_of_many_kinds_share():
    class Fields(Transformer):
        def __init__(self, **fields):
            self.__dict__.update(fields)

        def apply_batch(self, X):
            return X

    assert Fields(a=1, b="x", c=None, d=(1, (2.0, "y")), e=frozenset({3}),
                  f=jnp.float32, g=np.dtype("int8"),
                  h=jax.lax.Precision.HIGHEST).shares_program()


# ------------------------------------------------ trace-time reads are fields


def test_the_label_indicators_dtype_is_resolved_at_construction():
    y = np.array([0, 2, 1])
    prior = config.default_dtype
    try:
        config.default_dtype = "float32"
        single = ClassLabelIndicators(3)
        config.default_dtype = "bfloat16"
        half = ClassLabelIndicators(3)
        # Both called under the second setting: each keeps its own.
        assert single.batch_call(y).dtype == jnp.float32
        assert half.batch_call(y).dtype == jnp.bfloat16
        assert ClassLabelIndicators(3).batch_call(y).dtype == jnp.bfloat16
    finally:
        config.default_dtype = prior
    assert ClassLabelIndicators(3).batch_call(y).dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(single.batch_call(y)), [[1, -1, -1], [-1, -1, 1], [-1, 1, -1]])


@pytest.mark.parametrize("backend", ["tpu", "pallas"])
def test_the_fisher_encoders_dtype_is_resolved_at_construction(backend):
    X = _normal(0, 2, 7, 4)
    prior = config.default_dtype
    try:
        config.default_dtype = "float32"
        single = FisherVector(*_mixture(1), backend=backend)
        config.default_dtype = "bfloat16"
        half = FisherVector(*_mixture(1), backend=backend)
        assert single.batch_call(X).dtype == jnp.float32
        assert half.batch_call(X).dtype == jnp.bfloat16
    finally:
        config.default_dtype = prior


# ------------------------------------------------------- a second whole fit


def _images(seed, n=32, side=32, classes=4):
    r = _rng(seed)
    return LabeledData(r.uniform(0, 255, size=(n, side, side, 3)).astype(np.float32),
                       r.integers(0, classes, size=n).astype(np.int32))


def _frames(seed, n=128, d=24, classes=5):
    r = _rng(seed)
    return LabeledData(r.normal(size=(n, d)).astype(np.float32),
                       r.integers(0, classes, size=n).astype(np.int32))


IMAGENET = imagenet.resolve_scale(imagenet.ImageNetSiftLcsFVConfig(
    pca_dims=8, gmm_k=4, gmm_iters=3, descriptor_sample=500, num_iters=2,
    block_size=32))
TIMIT = timit.TimitConfig(num_features=32, num_cosines=4, block_size=32,
                          num_iters=2, num_phones=5)


CIFAR = cifar.RandomPatchCifarConfig(
    num_filters=16, patch_sample=500, patch_norm=10.0, pool_size=14, pool_stride=13,
    num_iters=1, lam=30.0, block_size=48, num_classes=4)


def _fit_cifar(seed):
    """New rows and a new seed: new patches, a new whitener, new filters."""
    import dataclasses

    train = _images(seed)
    return cifar.fit(dataclasses.replace(CIFAR, seed=seed), train.data, train.labels)


def _fit_imagenet(seed):
    _featurizer, fitted = imagenet.fit(IMAGENET, _images(seed), 4)
    return fitted


def _fit_timit(seed):
    return timit.fit(TIMIT, _frames(seed))


def _traced(fit, seed):
    """(fitted, compile requests, the root ``fit`` span's attributes)."""
    prior = config.trace
    config.trace = True
    reset_tracer()
    try:
        before = COMPILES.count
        fitted = fit(seed)
        requests = COMPILES.count - before
        spans = recorded_tracer().spans()
    finally:
        config.trace = prior
        reset_tracer()
    (root,) = [s for s in spans if s["name"] == "fit" and s.get("parent_id") is None]
    return fitted, requests, root["args"]


@pytest.mark.parametrize("fit, data, shared", [
    (_fit_imagenet, _images, 7), (_fit_timit, _frames, 2), (_fit_cifar, _images, 4),
], ids=["imagenet", "timit", "cifar"])
def test_a_second_fit_on_other_rows_compiles_nothing(fit, data, shared):
    first, _requests, _attrs = _traced(fit, 1)
    second, requests, attrs = _traced(fit, 2)
    assert requests == 0
    assert attrs["closure_program_calls"] == 0
    assert attrs["shared_program_calls"] == shared
    # Another fit, not the first one's answers.
    held_out = data(3).data
    before = COMPILES.count
    a, b = (np.asarray(f(held_out).get()) for f in (first, second))
    assert COMPILES.count - before <= 1  # the apply chain, once for both
    assert a.shape == b.shape
    if a.dtype.kind == "f":
        assert not np.allclose(a, b)


def _fit_cifar_kernel(seed):
    import dataclasses

    from keystone_tpu.pipelines.images import random_patch_cifar_kernel as kernel

    conf = kernel.RandomPatchCifarKernelConfig(
        num_filters=16, patch_sample=500, patch_norm=10.0, pool_size=14, pool_stride=13,
        lam=1.0, block_size=16, gamma=1e-3, num_epochs=2, num_classes=4)
    train = _images(seed)
    return kernel.fit(dataclasses.replace(conf, seed=seed), train.data, train.labels)


@pytest.mark.parametrize("fit", [_fit_imagenet, _fit_timit, _fit_cifar, _fit_cifar_kernel],
                         ids=["imagenet", "timit", "cifar", "cifar-kernel"])
def test_the_device_scopes_add_no_trace_and_no_compile_request_to_a_second_fit(fit):
    """A ``jax.named_scope`` acts where a program is traced and is no part
    of what finds the program again: the second fit of each pipeline, whose
    solver, kernel solver, chains and mixture fit all carry scopes, traces,
    lowers and compiles nothing, and traces none of those programs."""
    prior = config.trace
    config.trace = True
    reset_tracer()
    try:
        fit(1)
        mark = len(recorded_tracer().spans())
        before = COMPILES.count
        fit(2)
        requests = COMPILES.count - before
        second = recorded_tracer().spans()[mark:]
    finally:
        config.trace = prior
        reset_tracer()
    assert requests == 0
    assert [s["name"] for s in second if s["name"] in ("jax.lower", "jax.compile")] == []
    # What a second fit traces again is eager one-operation wrappers of the
    # pipelines' host code (``less``, ``multiply``), as before the scopes.
    retraced = {s["args"]["fun_name"] for s in second if s["name"] == "jax.trace"}
    scoped = {"local", "_fit_gmm", "_fit_kmeans", "_conv_rectify_pool"}
    assert not retraced & scoped
    assert not any(name.startswith("apply_") for name in retraced)
    assert sum(s["name"] == "fit" for s in second) == 1


def test_a_pickled_imagenet_pipeline_scores_the_same(tmp_path):
    from keystone_tpu.workflow.serialization import load_pipeline, save_pipeline

    fitted = _fit_imagenet(5)
    held_out = _images(6).data
    want = np.asarray(fitted(held_out).get())
    path = str(tmp_path / "imagenet.pkl")
    save_pipeline(fitted, path)
    restored = load_pipeline(path)
    before = COMPILES.count
    np.testing.assert_array_equal(np.asarray(restored(held_out).get()), want)
    # Found by structure: the first pipeline's programs. At most the map's
    # is compiled again, its weights coming back on one device where the
    # solver had left them across the test mesh.
    assert COMPILES.count - before <= 1
    for was, now in zip(fitted.transformers(), restored.transformers()):
        assert now._jitted().program is was._jitted().program
    mapper = [t for t in restored.transformers()
              for s in getattr(t, "stages", [t]) if isinstance(s, BlockLinearMapper)]
    assert mapper and all(t.shares_program() for t in restored.transformers())


def test_a_fit_leaves_nothing_for_the_cycle_collector():
    """A fit that traces nothing allocates few Python objects, so the cycle
    collector seldom runs: an array held only by a reference cycle (a
    graph's dataset node under a self-calling inner function, as
    ``structural_hash`` had) would stay on the device for fits on end.
    With the collector off, what is live after a fit is the fitted
    pipeline's own arrays."""
    import gc

    _fit_timit(1)  # programs and memos warm
    gc.collect()
    gc.disable()
    try:
        before = {id(a) for a in jax.live_arrays()}
        fitted = _fit_timit(2)
        own = {id(a) for a in jax.tree_util.tree_leaves(fitted.transformers())}
        left = [a.shape for a in jax.live_arrays()
                if id(a) not in before and id(a) not in own]
    finally:
        gc.enable()
    assert left == []
