"""``timit.fit`` and the mechanism it forced: a transformer's arrays are
arguments of its jitted program, not constants in it.

Tiny widths on the CPU (the 8-device fake mesh of ``conftest.py``, so the
sharded lowering runs too). The plain reference is the benchmark adapter's
(``benchmark/configs/timit-cosine-rf.py``), loaded by path as
``benchmark/tests`` load it; it imports nothing of the program.
"""

import importlib.util
import os
import pickle
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.config import config
from keystone_tpu.nodes.learning.linear_mapper import LinearMapper
from keystone_tpu.nodes.stats import CosineRandomFeatures, StandardScaler
from keystone_tpu.nodes.stats.normalizer import L2Normalizer
from keystone_tpu.nodes.stats.scalers import StandardScalerModel
from keystone_tpu.pipelines.speech import timit
from keystone_tpu.utils.metrics import (
    CompileEventCounter,
    program_counters,
    recorded_tracer,
    reset_tracer,
)
from keystone_tpu.workflow import FusedTransformer
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.optimizer import ChainFusionRule

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
SIZES = {
    "input_dim": 24, "block_features": 32, "cosine_blocks": 4, "block_size": 32,
    "num_classes": 7, "distribution": "gaussian", "gamma": 0.2, "num_iters": 5,
    "lam": 0.1, "rows": 512, "held_out_rows": 64,
}
# One compile oracle a process (registration is permanent).
COMPILES = CompileEventCounter()


@pytest.fixture(scope="module")
def adapter():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "timit_cosine_rf_adapter", os.path.join(BENCH, "configs", "timit-cosine-rf.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _frames(n=64, d=24, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def _chain(seed, d=24, features=128, blocks=4, X=None):
    X = _frames(d=d) if X is None else X
    return FusedTransformer([
        StandardScaler().fit(X),
        CosineRandomFeatures.create(d, features, gamma=0.2, seed=seed, blocks=blocks),
    ])


def _gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# ------------------------------------------------- fit against the reference


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_fit_matches_the_plain_reference(adapter, seed):
    data = adapter.make_data(seed, SIZES)
    fitted = adapter.fit(data, SIZES)
    answers = adapter.answers(fitted, data, SIZES)
    reference = adapter.reference(data, SIZES, answers)
    assert answers["facts"] == adapter.expected_facts(SIZES)
    # float32 on both sides: a 24-term product, a sum and a cosine round to
    # a few 1e-7 of a feature of order 1.
    assert _gap(answers["features"], reference["features"]) < 1e-5
    # Two float32 solves that differ in algorithm (the program's cached
    # explicit inverse, the reference's Cholesky solve a visit) on grams
    # whose condition number is about 1e4: 1e-7 x 1e4, with room.
    assert _gap(answers["scores"], reference["scores"]) < 1e-3
    # The stages the benchmark reads: scaler | cosines fused, then the map.
    names = [type(s).__name__ for t in fitted.transformers()
             for s in getattr(t, "stages", [t])]
    assert names == ["StandardScalerModel", "CosineRandomFeatures",
                     "BlockLinearMapper", "MaxClassifier"]


def test_fit_predicts_the_classes_it_was_given(adapter):
    data = adapter.make_data(3, SIZES)
    fitted = adapter.fit(data, SIZES)
    predicted = np.asarray(fitted(data["x"]).get())
    assert (predicted == data["y"]).mean() > 0.9


# --------------------------------------------- arrays are arguments


def _constants(text):
    """Element counts of the constants in a lowered module's text."""
    counts = []
    for shape in re.findall(r"stablehlo\.constant[^\n]*?: tensor<([^>]*)>", text):
        dims = [int(n) for n in shape.split("x")[:-1]]
        counts.append(int(np.prod(dims)) if dims else 1)
    return counts


def test_the_chains_program_holds_no_constant_of_the_projections_size():
    X = _frames()
    chain = _chain(1, X=X)
    text = chain._jitted().lower(X).as_text()
    assert "jit_apply_StandardScalerModel_CosineRandomFeatures" in text
    # mean, std, W, b, X: five arguments, and no constant beyond a scalar.
    assert len(re.findall(r"%arg\d+: tensor", text)) == 5
    assert all(n <= 1 for n in _constants(text)), _constants(text)
    # The sharded lowering too.
    from keystone_tpu.utils.mesh import SpecLayout

    layout = SpecLayout.for_mesh()
    sharded = chain._jitted_sharded(layout).lower(layout.put(X)).as_text()
    # The same five into the program, and again into the body that
    # ``shard_map`` runs a shard's rows through (PR 38).
    (main,) = re.findall(r"func\.func public @main\(([^\n]*)", sharded)
    assert len(re.findall(r"%arg\d+: tensor", main)) == 5
    assert len(re.findall(r"%arg\d+: tensor", sharded)) == 10
    assert all(n <= 1 for n in _constants(sharded))


def test_a_chain_that_names_no_arrays_shares_one_program():
    """A chain with no array at all takes the shared path too: its static
    part hashes by value, so two instances are one program, named after
    the chain."""
    X = _frames()
    one, two = (FusedTransformer([L2Normalizer()]) for _ in range(2))
    assert jax.tree_util.tree_leaves(one) == [] and one.shares_program()
    assert "jit_apply_L2Normalizer" in one._jitted().lower(X).as_text()
    a = np.asarray(one.batch_call(X))
    before = COMPILES.count
    np.testing.assert_array_equal(np.asarray(two.batch_call(X)), a)
    assert COMPILES.count == before
    assert one._jitted().program is two._jitted().program
    np.testing.assert_allclose(a, X / np.linalg.norm(X, axis=1, keepdims=True),
                               rtol=1e-6)
    # Another value of the field is another program of the same callable.
    loose = FusedTransformer([L2Normalizer(eps=1e-3)])
    assert loose._jitted().program is one._jitted().program
    loose.batch_call(X)
    assert COMPILES.count > before


def test_two_seeds_share_one_executable():
    X = _frames()
    first, second = _chain(1, X=X), _chain(2, X=X)
    a = np.asarray(first.batch_call(X))
    before = COMPILES.count
    b = np.asarray(second.batch_call(X))
    assert COMPILES.count == before  # no compile request: the same program
    assert first._jitted().program is second._jitted().program
    assert not np.allclose(a, b)
    mean, std = np.asarray(second.stages[0].mean), np.asarray(second.stages[0].std)
    W, phases = np.asarray(second.stages[1].W), np.asarray(second.stages[1].b)
    np.testing.assert_allclose(b, np.cos((X - mean) / std @ W + phases), atol=2e-6)


def test_another_width_is_another_program_of_the_same_callable():
    X = _frames()
    wide = _chain(1, features=256, X=X)
    assert wide._jitted().program is _chain(1, X=X)._jitted().program
    assert np.asarray(wide.batch_call(X)).shape == (64, 256)


def test_other_fields_keep_programs_apart():
    """What ``apply_batch`` reads beside the arrays is part of the key."""

    class Scaled(CosineRandomFeatures):
        def __init__(self, W, b, scale):
            super().__init__(W, b)
            self.scale = scale

        def apply_batch(self, X):
            return self.scale * super().apply_batch(X)

    node = CosineRandomFeatures.create(24, 64, seed=0)
    X = _frames()
    two = np.asarray(Scaled(node.W, node.b, 2.0).batch_call(X))
    before = COMPILES.count
    three = np.asarray(Scaled(node.W, node.b, 3.0).batch_call(X))
    assert COMPILES.count > before  # another value of the field: traced anew
    np.testing.assert_allclose(three, 1.5 * two, rtol=1e-6)
    other = node.W + 1.0
    before = COMPILES.count
    Scaled(other, node.b, 2.0).batch_call(X)
    assert COMPILES.count == before  # other arrays, the same field
    # 2 == 2.0, and they are two programs all the same: the type is in the key.
    np.testing.assert_allclose(np.asarray(Scaled(node.W, node.b, 2).batch_call(X)), two,
                               rtol=1e-6)
    assert COMPILES.count > before


def test_the_arrays_are_read_at_each_call():
    X = _frames()
    node = CosineRandomFeatures.create(24, 64, seed=0)
    before = np.asarray(node.batch_call(X))
    node.b = node.b + 0.5
    after = np.asarray(node.batch_call(X))
    np.testing.assert_allclose(
        after, np.cos(X @ np.asarray(node.W) + np.asarray(node.b)), atol=2e-6)
    assert not np.allclose(before, after)


def test_a_scaler_without_a_deviation_has_one_array():
    X = _frames()
    model = StandardScaler(normalize_std_dev=False).fit(X)
    assert jax.tree_util.tree_leaves(model) == [model.mean]
    np.testing.assert_allclose(np.asarray(model.batch_call(X)), X - X.mean(axis=0),
                               atol=1e-6)


def test_a_stage_with_undeclared_arrays_keeps_its_own_program():
    """A chain with a stage whose arrays are not named has a static part
    that does not hash: it is jitted as the closure it is (every array a
    constant, the module ``jit_apply_batch``), and the optimizer's memo
    keeps it alive as before."""
    X = _frames()
    W = np.random.default_rng(1).normal(size=(128, 3)).astype(np.float32)

    class Undeclared(LinearMapper):
        array_fields = ()

    def chain():
        c = _chain(1, X=X)
        return FusedTransformer(c.stages + [Undeclared(W)])

    one, two = chain(), chain()
    assert jax.tree_util.tree_leaves(one) and not one.shares_program()
    assert one._jitted() is one._jitted() is not two._jitted()
    assert "jit_apply_batch" in one._jitted().lower(X).as_text()
    np.testing.assert_allclose(np.asarray(one.batch_call(X)),
                               np.asarray(two.batch_call(X)), atol=1e-6)
    assert _chain(1, X=X).shares_program()


def test_argument_bytes_are_counted_a_call():
    X = _frames()
    chain = _chain(1, X=X)
    before = program_counters.get("argument_bytes")
    chain.batch_call(X)
    assert program_counters.get("argument_bytes") - before == (24 * 128 + 128 + 2 * 24) * 4


def test_the_fuse_memo_does_not_pin_a_shared_chain(adapter):
    """Each fit draws a projection of its own; the optimizer's memo of
    fused chains must not keep them (0.36 GB each at TIMIT's width)."""
    data = adapter.make_data(5, SIZES)
    for _ in range(2):
        adapter.fit(data, SIZES)
    rules = [r for _n, batch, _i in PipelineEnv.get().optimizer.batches for r in batch
             if isinstance(r, ChainFusionRule)]
    assert rules
    held = [s for rule in rules for f in rule._fuse_cache.values() for s in f.stages]
    assert not any(isinstance(s, CosineRandomFeatures) for s in held)


# ------------------------------------------------ identity and persistence


def test_a_pickled_pipeline_gives_the_same_scores(adapter, tmp_path):
    from keystone_tpu.workflow.serialization import load_pipeline, save_pipeline

    data = adapter.make_data(9, SIZES)
    fitted = adapter.fit(data, SIZES)
    want = adapter.answers(fitted, data, SIZES)
    path = str(tmp_path / "timit.pkl")
    save_pipeline(fitted, path)
    restored = load_pipeline(path)
    got = adapter.answers(restored, data, SIZES)
    np.testing.assert_array_equal(got["scores"], want["scores"])
    np.testing.assert_array_equal(np.asarray(restored(data["x_held_out"]).get()),
                                  np.asarray(fitted(data["x_held_out"]).get()))
    # A warm transformer pickles without its program and finds it again.
    chain = fitted.transformers()[0]
    chain.batch_call(data["x_held_out"])
    clone = pickle.loads(pickle.dumps(chain))
    assert "_jit_cache" not in clone.__dict__
    assert clone._jitted().program is chain._jitted().program


def test_signatures_and_digests_keep_their_identity():
    a = CosineRandomFeatures.create(24, 128, gamma=0.2, seed=3, blocks=4)
    b = CosineRandomFeatures.create(24, 128, gamma=0.2, seed=3, blocks=4)
    assert a.signature() == b.signature()
    assert a.signature() != CosineRandomFeatures.create(
        24, 128, gamma=0.2, seed=4, blocks=4).signature()
    assert a.signature() != CosineRandomFeatures.create(
        24, 128, gamma=0.2, seed=3, blocks=1).signature()
    assert a.chain_digest("x") == b.chain_digest("x") is not None
    fused = FusedTransformer([a, L2Normalizer()])
    assert fused.chain_hash(7) == L2Normalizer().chain_hash(a.chain_hash(7))


def test_blocks_are_drawn_one_by_one_in_block_order():
    from keystone_tpu.nodes.stats.random_features import _draw

    node = CosineRandomFeatures.create(24, 128, gamma=0.2, seed=3, blocks=4)
    key = jax.random.PRNGKey(3)
    for i in range(4):
        W, b = _draw(jax.random.fold_in(key, i), 24, 32, "gaussian", jnp.float32)
        np.testing.assert_array_equal(np.asarray(node.W[:, 32 * i:32 * i + 32]),
                                      np.asarray(W * 0.2))
        np.testing.assert_array_equal(np.asarray(node.b[32 * i:32 * i + 32]),
                                      np.asarray(b))
    # One block is the draw it always was.
    kw, kb = jax.random.split(jax.random.PRNGKey(3))
    one = CosineRandomFeatures.create(24, 128, gamma=0.2, seed=3)
    np.testing.assert_array_equal(
        np.asarray(one.W), np.asarray(jax.random.normal(kw, (24, 128)) * 0.2))
    with pytest.raises(ValueError):
        CosineRandomFeatures.create(24, 100, blocks=3)


def test_cosine_features_name_the_highest_precision():
    X = _frames()
    text = CosineRandomFeatures.create(24, 64, seed=0)._jitted().lower(X).as_text()
    assert re.search(r"dot_general[^\n]*precision = \[HIGHEST, HIGHEST\]", text)


# ------------------------------------------------------------------- spans


def test_fit_records_its_root_and_the_random_features_span(adapter):
    prior = config.trace
    config.trace = True
    reset_tracer()
    try:
        data = adapter.make_data(13, SIZES)
        adapter.fit(data, SIZES)
        spans = recorded_tracer().spans()
    finally:
        config.trace = prior
        reset_tracer()
    roots = [s for s in spans if s["name"] == "fit" and s.get("parent_id") is None]
    assert len(roots) == 1
    assert roots[0]["args"]["pipeline"] == "timit" and roots[0]["args"]["rows"] == 512
    (rf,) = [s for s in spans if s["name"] == "features.random"]
    assert rf["parent_id"] == roots[0]["id"]
    assert {k: rf["args"][k] for k in ("rows", "dim_in", "dim_out", "blocks", "bytes")} == {
        "rows": 512, "dim_in": 24, "dim_out": 128, "blocks": 4,
        "bytes": (24 * 128 + 128 + 2 * 24) * 4}
    under_root = {s["name"] for s in spans if s.get("root_id") == roots[0]["id"]}
    assert {"pipeline.apply", "pipeline.fit", "solver.stack", "solver.factor",
            "solver.epochs"} <= under_root
    (epochs,) = [s for s in spans if s["name"] == "solver.epochs"]
    assert epochs["args"]["blocks"] == 4 and epochs["args"]["epochs"] == 5


def test_the_cli_follows_the_configuration():
    out = timit.main(["--synthetic-n", "512", "--num-features", "64", "--num-cosines", "2",
                      "--block-size", "64", "--num-iters", "2", "--num-phones", "6"])
    assert out["test_accuracy"] > 0.5
