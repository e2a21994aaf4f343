"""Core workflow tests: composition algebra, laziness, fitting, fusion, memo.

Mirrors the reference's workflow suites (PipelineSuite, EstimatorSuite,
TransformerSuite [unverified paths]).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.workflow import (
    Estimator,
    LabelEstimator,
    Pipeline,
    PipelineEnv,
    Transformer,
)
from keystone_tpu.workflow.operators import TransformerOperator
from keystone_tpu.workflow.pipeline import FusedTransformer


class Plus(Transformer):
    def __init__(self, c):
        self.c = c

    def apply_batch(self, X):
        return X + self.c


class Times(Transformer):
    def __init__(self, c):
        self.c = c

    def apply_batch(self, X):
        return X * self.c


class MeanShift(Estimator):
    """Fits the mean of the data; transformer subtracts it."""

    def __init__(self):
        self.fit_count = 0

    def fit(self, data):
        self.fit_count += 1
        return Plus(-jnp.mean(jnp.asarray(data), axis=0))


class ScaleToLabels(LabelEstimator):
    def __init__(self):
        self.fit_count = 0

    def fit(self, data, labels):
        self.fit_count += 1
        scale = jnp.mean(jnp.asarray(labels)) / jnp.mean(jnp.asarray(data))
        return Times(scale)


def test_transformer_batch_and_datum():
    t = Plus(2.0)
    X = np.arange(6.0).reshape(3, 2)
    np.testing.assert_allclose(t(X), X + 2.0)
    np.testing.assert_allclose(t.apply(np.ones(2)), np.ones(2) + 2.0)


def test_and_then_composition():
    p = Plus(1.0).and_then(Times(3.0)).and_then(Plus(-2.0))
    X = np.ones((4, 2))
    out = p(X).get()
    np.testing.assert_allclose(out, (1.0 + 1.0) * 3.0 - 2.0)


def test_pipeline_is_lazy():
    calls = []

    class Probe(Transformer):
        jittable = False

        def apply_batch(self, X):
            calls.append(1)
            return X

    p = Probe().to_pipeline()
    ds = p(np.ones((2, 2)))
    assert calls == []
    ds.get()
    assert calls == [1]
    ds.get()  # memoized
    assert calls == [1]


def test_estimator_with_data():
    est = MeanShift()
    X = np.array([[1.0, 2.0], [3.0, 4.0]])
    p = est.with_data(X)
    out = p(X).get()
    np.testing.assert_allclose(out, X - X.mean(axis=0), atol=1e-6)
    assert est.fit_count == 1


def test_to_dot_export():
    est = MeanShift()
    X = np.array([[1.0, 2.0], [3.0, 4.0]])
    p = est.with_data(X)
    dot = p.to_dot()
    assert dot.startswith("digraph pipeline {") and dot.endswith("}")
    assert "MeanShift.fit" in dot and "Delegating" in dot
    assert "input" in dot  # the free source renders as a diamond
    assert "->" in dot


def test_fit_cache_across_applications():
    est = MeanShift()
    X = np.array([[1.0, 2.0], [3.0, 4.0]])
    p = est.with_data(X)
    p(X).get()
    p(X * 2).get()
    assert est.fit_count == 1  # fitted-prefix reuse


def test_label_estimator():
    est = ScaleToLabels()
    X = np.full((4, 1), 2.0)
    y = np.full((4, 1), 6.0)
    p = est.with_data(X, y)
    out = p(np.ones((2, 1))).get()
    np.testing.assert_allclose(out, 3.0 * np.ones((2, 1)), atol=1e-5)


def test_and_then_estimator_fits_on_pipeline_output():
    # pipeline.and_then(est, data): estimator sees pipeline(data)
    est = MeanShift()
    X = np.array([[0.0], [2.0]])  # after Plus(1): mean = 2
    p = Plus(1.0).and_then(est, X)
    out = p(np.array([[5.0]])).get()
    np.testing.assert_allclose(out, np.array([[4.0]]), atol=1e-6)  # 5+1-2


def test_gather_concatenates_branches():
    b1 = Plus(1.0).to_pipeline()
    b2 = Times(2.0).to_pipeline()
    p = Pipeline.gather([b1, b2])
    X = np.ones((3, 2))
    out = p(X).get()
    assert out.shape == (3, 4)
    np.testing.assert_allclose(out[:, :2], 2.0)
    np.testing.assert_allclose(out[:, 2:], 2.0)


def test_gather_shares_common_prefix_computation():
    calls = []

    class Probe(Transformer):
        jittable = False

        def apply_batch(self, X):
            calls.append(1)
            return X

    base = Probe().to_pipeline()
    p = Pipeline.gather([base.and_then(Plus(1.0)), base.and_then(Times(2.0))])
    p(np.ones((2, 2))).get()
    # Structural-hash memo dedups the copied Probe nodes within one execution.
    assert calls == [1]


def test_fit_returns_transformer_only_pipeline():
    est = MeanShift()
    X = np.array([[1.0], [3.0]])
    p = Plus(0.0).and_then(est, X)
    fitted = p.fit()
    ts = fitted.transformers()
    assert all(isinstance(t, Transformer) for t in ts)
    out = fitted(np.array([[2.0]])).get()
    np.testing.assert_allclose(out, np.array([[0.0]]), atol=1e-6)


def test_chain_fusion_rule():
    p = Plus(1.0).and_then(Times(3.0)).and_then(Plus(-2.0))
    env = PipelineEnv.get()
    ds = p(np.ones((2, 2)))
    g = env.optimizer.execute(ds.graph, [ds.sink])
    t_ops = [
        op for op in g.operators.values() if isinstance(op, TransformerOperator)
    ]
    assert len(t_ops) == 1
    assert isinstance(t_ops[0].transformer, FusedTransformer)
    assert len(t_ops[0].transformer.stages) == 3
    np.testing.assert_allclose(ds.get(), 4.0 * np.ones((2, 2)))


def test_fusion_preserves_prefix_hash():
    est = MeanShift()
    X = np.ones((4, 2))
    feats = Plus(1.0).and_then(Times(2.0))
    p = feats.and_then(est, X)
    p(X).get()
    assert est.fit_count == 1
    # Re-applying through a different graph copy must not refit.
    p(X * 3).get()
    assert est.fit_count == 1


def test_apply_datum():
    p = Plus(1.0).and_then(Times(2.0))
    out = p.apply_datum(np.array([1.0, 2.0]))
    np.testing.assert_allclose(out, np.array([4.0, 6.0]))


def test_host_transformer_on_lists():
    class Upper(Transformer):
        jittable = False

        def apply(self, x):
            return x.upper()

    p = Upper().to_pipeline()
    assert p(["ab", "cd"]).get() == ["AB", "CD"]


def test_fusion_is_hash_invariant():
    # The same logical prefix must hash equal whether or not it got fused.
    from keystone_tpu.workflow.graph import structural_hash
    from keystone_tpu.workflow import PipelineEnv

    t1, t2 = Plus(1.0), Times(2.0)
    X = np.ones((2, 2))
    p = t1.and_then(t2)
    ds = p(X)
    env = PipelineEnv.get()
    fused_g = env.optimizer.execute(ds.graph, [ds.sink])

    def no_src(s):
        raise AssertionError

    h_unfused = structural_hash(ds.graph, ds.sink, no_src)
    # sink id survives optimization (merge rule preserves targets)
    h_fused = structural_hash(fused_g, ds.sink, no_src)
    assert h_unfused == h_fused


def test_fitted_pipeline_drops_training_data():
    from keystone_tpu.workflow.operators import DatasetOperator, EstimatorOperator

    est = MeanShift()
    X = np.ones((8, 2))
    p = Plus(0.0).and_then(est, X)
    fitted = p.fit()
    ops = list(fitted.graph.operators.values())
    assert not any(isinstance(o, (DatasetOperator, EstimatorOperator)) for o in ops)


def test_fit_cache_pins_objects_and_evicts_with_estimator():
    import gc

    from keystone_tpu.workflow import PipelineEnv

    est = MeanShift()
    X = np.ones((4, 2))
    est.with_data(X)(X).get()
    env = PipelineEnv.get()
    (entry,) = env.fit_cache.values()
    _fitted, pins, keeper = entry
    # Data is pinned (id-reuse safety); the estimator itself is held weakly.
    assert any(o is X for o in pins)
    assert keeper() is est
    # Dropping the estimator evicts the entry (and frees the pinned data).
    del est, entry, keeper, _fitted
    gc.collect()
    assert env.fit_cache == {}


@pytest.mark.parametrize("shared", [True, False])
def test_repeated_apply_reuses_fused_jit(shared):
    """Every ``apply`` optimizes a fresh graph copy, and the copies of one
    logical chain meet one jitted program: a chain whose fields hash by
    value by its structure (new fused objects, nothing memoised), a
    closure chain (an array it does not name) through the optimizer's memo
    of fused objects."""
    first = Plus(1.0 if shared else jnp.ones(2))
    p = first.and_then(Times(2.0)).and_then(Plus(0.5))
    X = np.ones((2, 2))
    fused = []
    from keystone_tpu.workflow import PipelineEnv
    from keystone_tpu.workflow.operators import TransformerOperator

    for _ in range(3):
        ds = p(X)
        g = PipelineEnv.get().optimizer.execute(ds.graph, [ds.sink])
        fused += [op.transformer for op in g.operators.values()
                  if isinstance(op, TransformerOperator)]
        ds.get()
    assert len(fused) == 3 and all(f.shares_program() == shared for f in fused)
    assert len({id(f) for f in fused}) == (3 if shared else 1)
    assert len({id(f._jitted().program) for f in fused}) == 1


def test_apply_datum_respects_batch_contract():
    class RowNormalize(Transformer):
        def apply_batch(self, X):
            return X / X.sum(axis=1, keepdims=True)

    out = RowNormalize().to_pipeline().apply_datum(np.array([1.0, 3.0]))
    np.testing.assert_allclose(out, [0.25, 0.75])


def test_estimator_with_labels_rejected():
    est = MeanShift()
    with pytest.raises(TypeError, match="LabelEstimator"):
        Plus(1.0).and_then(est, np.ones((2, 1)), np.ones((2, 1)))


def test_dataset_sharding_respects_placement_and_dtype():
    import jax
    import jax.numpy as jnp

    from keystone_tpu.utils.mesh import replicated_sharding
    from keystone_tpu.workflow.operators import DatasetOperator

    # Host numpy numeric batch: sharded over the mesh.
    X = np.ones((64, 4), dtype=np.float32)
    out = DatasetOperator(X).execute([])
    assert len(out.sharding.device_set) == len(jax.devices())
    # Explicitly replicated device array: placement preserved.
    rep = jax.device_put(jnp.ones((64, 4)), replicated_sharding())
    out2 = DatasetOperator(rep).execute([])
    assert out2.sharding == rep.sharding
    # String array: untouched (host transformer input).
    s = np.asarray(["a"] * 64)
    assert DatasetOperator(s).execute([]) is s
    # Non-divisible rows: placement DEFERRED to the fused chain's
    # mask-pad path (jax refuses an uneven device_put) — the operator
    # hands the host batch through unchanged and counts the deferral.
    odd = np.ones((65, 4), dtype=np.float32)
    assert DatasetOperator(odd).execute([]) is odd


def test_stable_signatures_dedupe_rebuilt_pipelines():
    from keystone_tpu.nodes.stats import PaddedFFT, RandomSignNode
    from keystone_tpu.nodes.util import Cacher

    calls = []

    class CountingRectifier(Transformer):
        """Stable-signature host stage so recomputation is observable."""

        jittable = False

        def signature(self):
            return self.stable_signature()

        def apply_batch(self, X):
            calls.append(1)
            return np.maximum(np.asarray(X), 0.0)

    X = np.random.default_rng(0).normal(size=(64, 32)).astype(np.float32)

    def build():
        # Two separately-constructed but identical featurizers.
        return (
            RandomSignNode.create(32, seed=5)
            .and_then(PaddedFFT())
            .and_then(CountingRectifier())
            .and_then(Cacher())
        )

    a, b = build(), build()
    out_a = np.asarray(a(X).get())
    out_b = np.asarray(b(X).get())  # session-cache hit via stable signatures
    np.testing.assert_array_equal(out_a, out_b)
    from keystone_tpu.workflow import PipelineEnv

    assert len(PipelineEnv.get().node_cache) == 1  # one shared entry
    # The cache hit must CUT the second execution: upstream never reruns.
    assert calls == [1]


def test_stable_signature_subclass_never_collides():
    from keystone_tpu.nodes.util import Identity

    class Shifted(Identity):
        def apply_batch(self, X):
            return X + 1.0

    assert Identity().signature() != Shifted().signature()


class TestDeepGraphNodeOptimization:
    """NodeOptimizationRule must cost-model-dispatch estimators whose inputs
    are transformer subgraphs, not just directly-attached datasets, by
    running the sampling profiler over the prefix (the reference profiles
    sampled prefixes for stats anywhere in the DAG — SURVEY.md §3.5)."""

    def _deep_pipeline(self, n=131072, d=48, k=8):
        from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator

        rng = np.random.default_rng(0)
        X = rng.normal(size=(n, d)).astype(np.float32)
        Y = rng.normal(size=(n, k)).astype(np.float32)
        feats = Plus(1.0).and_then(Times(2.0))
        est = LeastSquaresEstimator(lam=1e-3)
        p = est.with_data(feats(X), Y)
        return p, est

    def test_estimator_behind_featurizer_chain_is_dispatched(self):
        from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
        from keystone_tpu.nodes.learning.linear_mapper import LinearMapEstimator
        from keystone_tpu.workflow.operators import EstimatorOperator
        from keystone_tpu.workflow.rules import NodeOptimizationRule

        p, est = self._deep_pipeline()
        g = NodeOptimizationRule().apply(p.graph, [p.sink])
        concrete = [
            op.estimator
            for op in g.operators.values()
            if isinstance(op, EstimatorOperator)
            and not isinstance(op.estimator, LeastSquaresEstimator)
        ]
        # n=131072 x d=48 exceeds the tiny-problem bar, so the cost model
        # must choose normal equations. Had the rule used the RAW 64-row
        # sample shape instead of the row-scale-corrected one, it would
        # have picked the local solver — this asserts the scaling too.
        assert len(concrete) == 1
        assert isinstance(concrete[0], LinearMapEstimator)
        assert est.last_choice.name == "normal"

    def test_labels_behind_transformer_resolve_k(self):
        from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
        from keystone_tpu.nodes.util.labels import ClassLabelIndicators
        from keystone_tpu.workflow.operators import EstimatorOperator
        from keystone_tpu.workflow.rules import NodeOptimizationRule

        rng = np.random.default_rng(0)
        n, d = 131072, 48
        X = rng.normal(size=(n, d)).astype(np.float32)
        y_int = rng.integers(0, 10, size=n)
        est = LeastSquaresEstimator(lam=1e-3)
        p = est.with_data(X, ClassLabelIndicators(10).to_pipeline()(y_int))
        g = NodeOptimizationRule().apply(p.graph, [p.sink])
        replaced = [
            op.estimator
            for op in g.operators.values()
            if isinstance(op, EstimatorOperator)
            and not isinstance(op.estimator, LeastSquaresEstimator)
        ]
        # Without the sampled prefix the one-hot width k would be unknown
        # (labels_shape=None -> fit-time dispatch, no replacement).
        assert len(replaced) == 1

    def test_deep_graph_replacement_memoized_across_passes(self):
        from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
        from keystone_tpu.workflow.operators import EstimatorOperator
        from keystone_tpu.workflow.rules import NodeOptimizationRule

        p, _est = self._deep_pipeline()
        rule = NodeOptimizationRule()
        g1 = rule.apply(p.graph, [p.sink])
        g2 = rule.apply(p.graph, [p.sink])

        def concrete(g):
            return [
                op.estimator
                for op in g.operators.values()
                if isinstance(op, EstimatorOperator)
                and not isinstance(op.estimator, LeastSquaresEstimator)
            ]

        c1, c2 = concrete(g1), concrete(g2)
        assert c1 and c2 and c1[0] is c2[0]

    def test_sampled_prefix_fit_does_not_mutate_user_estimator(self):
        """The sample run fits a COPY of upstream estimators: a profiling
        probe must not leak fitted state into user-held objects."""
        from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
        from keystone_tpu.workflow.rules import NodeOptimizationRule

        rng = np.random.default_rng(0)
        X = rng.normal(size=(131072, 48)).astype(np.float32)
        Y = rng.normal(size=(131072, 8)).astype(np.float32)
        upstream = MeanShift()
        ls = LeastSquaresEstimator(lam=1e-3)
        p = ls.with_data(upstream.with_data(X)(X), Y)
        NodeOptimizationRule().apply(p.graph, [p.sink])
        assert upstream.fit_count == 0  # probe fit ran on a copy

    def test_shape_memo_skips_resampling_across_passes(self):
        from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
        from keystone_tpu.workflow.rules import NodeOptimizationRule

        calls = []

        class Probe(Transformer):
            def signature(self):
                # Content-stable: the shape memo only serves digestable
                # prefixes (id-based ones are recomputed each pass).
                return self.stable_signature()

            def apply_batch(self, X):
                calls.append(len(X))
                return X

        rng = np.random.default_rng(0)
        X = rng.normal(size=(131072, 48)).astype(np.float32)
        Y = rng.normal(size=(131072, 8)).astype(np.float32)
        est = LeastSquaresEstimator(lam=1e-3)
        p = est.with_data(Probe().to_pipeline()(X), Y)
        rule = NodeOptimizationRule()
        rule.apply(p.graph, [p.sink])
        first = len(calls)
        assert first >= 1
        rule.apply(p.graph, [p.sink])  # memo hit: no re-execution
        assert len(calls) == first

    def test_unbound_source_prefix_skips_sampling(self):
        """An optimizable estimator whose data prefix reaches an unbound
        source can never be sampled or dispatched: the rule must skip it
        without paying a sample run (and without crashing)."""
        from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
        from keystone_tpu.workflow.operators import EstimatorOperator
        from keystone_tpu.workflow.rules import NodeOptimizationRule
        from keystone_tpu.workflow.graph import Graph, fresh_source_id
        from keystone_tpu.workflow.operators import (
            DatasetOperator,
            TransformerOperator,
        )

        rng = np.random.default_rng(0)
        Y = rng.normal(size=(256, 4)).astype(np.float32)
        g = Graph()
        src = fresh_source_id()
        g, t_id = g.add(TransformerOperator(Plus(1.0)), [src])
        g, y_id = g.add(DatasetOperator(Y), [])
        est = LeastSquaresEstimator(lam=1e-3)
        g, e_id = g.add(EstimatorOperator(est), [t_id, y_id])
        out = NodeOptimizationRule().apply(g, [e_id])
        assert isinstance(out.operators[e_id].estimator, LeastSquaresEstimator)

    def test_failing_sample_prefix_falls_back_to_fit_time_dispatch(self):
        """A prefix that can't execute on a 64-row sample must not crash
        optimization — the estimator keeps fit-time dispatch."""
        from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
        from keystone_tpu.workflow.operators import EstimatorOperator
        from keystone_tpu.workflow.rules import NodeOptimizationRule

        class MinBatch(Transformer):
            jittable = False

            def apply_batch(self, X):
                assert len(X) >= 1000, "needs full batch"
                return X

        rng = np.random.default_rng(0)
        X = rng.normal(size=(4096, 8)).astype(np.float32)
        Y = rng.normal(size=(4096, 2)).astype(np.float32)
        est = LeastSquaresEstimator(lam=1e-3)
        p = est.with_data(MinBatch().to_pipeline()(X), Y)
        g = NodeOptimizationRule().apply(p.graph, [p.sink])  # must not raise
        kept = [
            op.estimator
            for op in g.operators.values()
            if isinstance(op, EstimatorOperator)
        ]
        assert any(isinstance(e, LeastSquaresEstimator) for e in kept)

    def test_row_changing_prefix_defers_to_fit_time(self):
        """A row-aggregating prefix makes scaled-n meaningless: the rule
        must NOT dispatch from a fabricated n."""
        from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
        from keystone_tpu.workflow.operators import EstimatorOperator
        from keystone_tpu.workflow.rules import NodeOptimizationRule

        class Head32(Transformer):
            jittable = False

            def signature(self):
                return self.stable_signature()

            def apply_batch(self, X):
                return X[:32]  # row-changing: fixed-size head

        rng = np.random.default_rng(0)
        X = rng.normal(size=(131072, 48)).astype(np.float32)
        Y = rng.normal(size=(131072, 8)).astype(np.float32)
        est = LeastSquaresEstimator(lam=1e-3)
        p = est.with_data(Head32().to_pipeline()(X), Y)
        g = NodeOptimizationRule().apply(p.graph, [p.sink])
        kept = [
            op.estimator
            for op in g.operators.values()
            if isinstance(op, EstimatorOperator)
        ]
        # real fit sees n=32; a scaled n=65536 would have picked "normal".
        assert all(isinstance(e, LeastSquaresEstimator) for e in kept)

    def test_failed_sample_run_is_not_memoized(self):
        """A transient sample failure must not permanently disable
        optimize-time dispatch for that prefix."""
        from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
        from keystone_tpu.nodes.learning.linear_mapper import LinearMapEstimator
        from keystone_tpu.workflow.operators import EstimatorOperator
        from keystone_tpu.workflow.rules import NodeOptimizationRule

        fail = {"on": True}

        class Flaky(Transformer):
            jittable = False

            def signature(self):
                return self.stable_signature()

            def apply_batch(self, X):
                if fail["on"]:
                    raise RuntimeError("transient")
                return X

        rng = np.random.default_rng(0)
        X = rng.normal(size=(131072, 48)).astype(np.float32)
        Y = rng.normal(size=(131072, 8)).astype(np.float32)
        est = LeastSquaresEstimator(lam=1e-3)
        p = est.with_data(Flaky().to_pipeline()(X), Y)
        rule = NodeOptimizationRule()
        g1 = rule.apply(p.graph, [p.sink])  # fails -> fit-time dispatch kept
        assert all(
            isinstance(op.estimator, LeastSquaresEstimator)
            for op in g1.operators.values()
            if isinstance(op, EstimatorOperator)
        )
        fail["on"] = False
        g2 = rule.apply(p.graph, [p.sink])  # retry succeeds -> dispatched
        assert any(
            isinstance(op.estimator, LinearMapEstimator)
            for op in g2.operators.values()
            if isinstance(op, EstimatorOperator)
        )
