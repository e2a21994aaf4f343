"""Auxiliary subsystems: profiler, auto-cache, node-level optimization,
serialization, checkpoint/resume, metrics."""

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.workflow import Estimator, Pipeline, PipelineEnv, Transformer


class Plus(Transformer):
    def __init__(self, c):
        self.c = c
        self.calls = 0

    def apply_batch(self, X):
        self.calls += 1
        return X + self.c


class CountingHost(Transformer):
    jittable = False

    def __init__(self):
        self.calls = 0

    def apply_batch(self, X):
        self.calls += 1
        return np.asarray(X) * 2.0


def test_profiler_measures_nodes(rng):
    from keystone_tpu.workflow.cache import Profiler

    X = rng.normal(size=(256, 4)).astype(np.float32)
    ds = Plus(1.0).and_then(Plus(2.0))(X)
    profiles = Profiler(sample_rows=32).profile(ds.graph, [ds.sink])
    assert len(profiles) == 3  # dataset + 2 transformers
    for p in profiles.values():
        assert p.bytes > 0 and p.seconds >= 0
    # Scale estimate: 256 rows / 32 sampled.
    assert any(abs(p.scale - 8.0) < 1e-6 for p in profiles.values())


def test_explicit_cache_persists_across_executions(rng):
    host = CountingHost()
    X = rng.normal(size=(8, 3)).astype(np.float32)
    p = host.to_pipeline().cache()
    out1 = np.asarray(p(X).get())
    assert host.calls == 1
    # New application => new graph copy; the session cache must hit.
    out2 = np.asarray(p(X).get())
    assert host.calls == 1
    np.testing.assert_array_equal(out1, out2)


def test_auto_cache_rule_inserts_cache_nodes(rng):
    from keystone_tpu.workflow.cache import CacheOperator
    from keystone_tpu.workflow.rules import AutoCacheRule

    X = rng.normal(size=(128, 4)).astype(np.float32)
    base = CountingHost().to_pipeline()
    p = Pipeline.gather([base.and_then(Plus(1.0)), base.and_then(Plus(2.0))])
    ds = p(X)
    g = AutoCacheRule(budget_bytes=1 << 30, sample_rows=16).apply(
        ds.graph, [ds.sink]
    )
    cache_nodes = [
        op for op in g.operators.values() if isinstance(op, CacheOperator)
    ]
    assert cache_nodes  # profitable shared nodes got cached
    # Graph still executes correctly with caches inserted.
    out = PipelineEnv.get().executor.execute(g, ds.sink)
    assert np.asarray(out).shape == (128, 8)


def test_node_optimization_rule_swaps_estimator(rng):
    from keystone_tpu.nodes.learning import (
        LeastSquaresEstimator,
        LocalLeastSquaresEstimator,
    )
    from keystone_tpu.workflow.operators import EstimatorOperator

    X = rng.normal(size=(40, 5)).astype(np.float32)
    Y = rng.normal(size=(40, 2)).astype(np.float32)
    est = LeastSquaresEstimator(lam=0.1)
    p = est.with_data(X, Y)
    ds = p(X)
    g = PipelineEnv.get().optimizer.execute(ds.graph, [ds.sink])
    est_ops = [
        op for op in g.operators.values() if isinstance(op, EstimatorOperator)
    ]
    assert len(est_ops) == 1
    assert isinstance(est_ops[0].estimator, LocalLeastSquaresEstimator)
    assert est.last_choice.name == "local"


def test_save_load_fitted_pipeline(rng, tmp_path):
    from keystone_tpu.workflow.serialization import load_pipeline, save_pipeline

    class MeanShift(Estimator):
        def fit(self, data):
            return Plus(-jnp.mean(jnp.asarray(data), axis=0))

    X = rng.normal(size=(32, 4)).astype(np.float32)
    p = Plus(1.0).and_then(MeanShift(), X).fit()
    path = str(tmp_path / "model.pkl")
    save_pipeline(p, path)
    loaded = load_pipeline(path)
    np.testing.assert_allclose(
        np.asarray(loaded(X[:4]).get()),
        np.asarray(p(X[:4]).get()),
        atol=1e-6,
    )


def test_save_rejects_unfitted_pipeline(rng, tmp_path):
    from keystone_tpu.workflow.serialization import save_pipeline

    class E(Estimator):
        def fit(self, data):
            return Plus(0.0)

    p = E().with_data(np.ones((4, 2), dtype=np.float32))
    with pytest.raises(ValueError, match="unfitted"):
        save_pipeline(p, str(tmp_path / "x.pkl"))


def test_bcd_checkpoint_resume(rng, tmp_path):
    from keystone_tpu.linalg import RowMatrix, block_coordinate_descent
    from keystone_tpu.linalg.bcd import assemble_blocks

    X = rng.normal(size=(160, 16)).astype(np.float32)
    Y = rng.normal(size=(160, 2)).astype(np.float32)
    A, B = RowMatrix.from_array(X), RowMatrix.from_array(Y)
    ck = str(tmp_path / "bcd")
    # Full 4-epoch run without checkpointing = reference result.
    W_ref, blocks = block_coordinate_descent(A, B, 8, 4, lam=0.1)
    # Run 2 epochs with checkpointing, then "crash" and resume to 4.
    block_coordinate_descent(A, B, 8, 2, lam=0.1, checkpoint_dir=ck)
    W_resumed, _ = block_coordinate_descent(
        A, B, 8, 4, lam=0.1, checkpoint_dir=ck
    )
    np.testing.assert_allclose(
        assemble_blocks(W_resumed),
        assemble_blocks(W_ref),
        rtol=1e-4,
        atol=1e-4,
    )


def test_stage_timer_and_cost_analysis(rng):
    from keystone_tpu.utils.metrics import achieved_tflops, cost_analysis

    X = jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))
    cost = cost_analysis(lambda a: a @ a, X)
    # 2 n^3 FLOPs for a square matmul.
    assert cost["flops"] == pytest.approx(2 * 64**3, rel=0.1)
    perf = achieved_tflops(lambda a: a @ a, X, repeats=2)
    assert perf["tflops"] > 0


def test_fit_and_save_with_auto_cache_enabled(rng, tmp_path):
    from keystone_tpu.config import config
    from keystone_tpu.nodes.learning import LinearMapEstimator
    from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
    from keystone_tpu.workflow import PipelineEnv
    from keystone_tpu.workflow.serialization import load_pipeline, save_pipeline

    config.auto_cache = True
    PipelineEnv.reset()  # rebuild the optimizer with the auto-cache batch
    try:
        X = rng.normal(size=(60, 8)).astype(np.float32)
        y = rng.integers(0, 3, 60).astype(np.int32)
        p = (
            LinearMapEstimator(0.1)
            .with_data(X, ClassLabelIndicators(3)(y))
            .and_then(MaxClassifier())
            .fit()
        )
        path = str(tmp_path / "m.pkl")
        save_pipeline(p, path)  # must not see any estimator nodes
        loaded = load_pipeline(path)
        np.testing.assert_array_equal(
            np.asarray(loaded(X[:5]).get()), np.asarray(p(X[:5]).get())
        )
    finally:
        config.auto_cache = False
        PipelineEnv.reset()


def test_bcd_checkpoint_rejects_different_problem(rng, tmp_path):
    from keystone_tpu.linalg import RowMatrix, block_coordinate_descent
    from keystone_tpu.linalg.bcd import assemble_blocks

    ck = str(tmp_path / "bcd")
    X1 = rng.normal(size=(80, 8)).astype(np.float32)
    Y1 = rng.normal(size=(80, 2)).astype(np.float32)
    block_coordinate_descent(
        RowMatrix.from_array(X1), RowMatrix.from_array(Y1), 8, 2,
        lam=0.1, checkpoint_dir=ck,
    )
    # Same shapes, different data: stale checkpoint must NOT be restored.
    X2 = rng.normal(size=(80, 8)).astype(np.float32)
    Y2 = rng.normal(size=(80, 2)).astype(np.float32)
    W2, blocks = block_coordinate_descent(
        RowMatrix.from_array(X2), RowMatrix.from_array(Y2), 8, 2,
        lam=0.1, checkpoint_dir=ck,
    )
    W_fresh, _ = block_coordinate_descent(
        RowMatrix.from_array(X2), RowMatrix.from_array(Y2), 8, 2, lam=0.1
    )
    np.testing.assert_allclose(
        assemble_blocks(W2), assemble_blocks(W_fresh),
        rtol=1e-5, atol=1e-5,
    )


def test_gmm_fisher_estimator_tpu_backend_without_native(rng):
    from keystone_tpu.nodes.images.external import GMMFisherVectorEstimator

    X = np.concatenate(
        [rng.normal(-2, 0.5, (300, 4)), rng.normal(2, 0.8, (300, 4))]
    ).astype(np.float32)
    fv = GMMFisherVectorEstimator(k=2, em_iters=30, gmm_backend="tpu").fit(X)
    means = np.sort(np.asarray(fv.means)[:, 0])
    np.testing.assert_allclose(means, [-2, 2], atol=0.3)
    out = np.asarray(fv(rng.normal(size=(3, 20, 4)).astype(np.float32)))
    assert out.shape == (3, 2 * 2 * 4)


def test_cacher_node_parity(rng):
    from keystone_tpu.nodes.util import Cacher

    host = CountingHost()
    X = rng.normal(size=(4, 2)).astype(np.float32)
    p = host.to_pipeline().and_then(Cacher())
    p(X).get()
    p(X).get()
    assert host.calls == 1  # identical to pipeline.cache()


def test_pil_conversions(rng):
    from keystone_tpu.utils.image import from_pil, to_pil

    arr = rng.uniform(size=(10, 12, 3)).astype(np.float32)
    back = from_pil(to_pil(arr))
    assert back.shape == (10, 12, 3)
    np.testing.assert_allclose(back, arr, atol=0.5 / 255 + 1e-6)
    resized = from_pil(to_pil(arr), size=6)
    assert resized.shape == (6, 6, 3)
