"""Stage-parallel DAG execution (workflow/executor.py _ParallelWalk).

Pins the ISSUE-10 tentpole contract:

1. Bit-identity: every canonical pipeline (MNIST FFT, CIFAR random
   patch, VOC SIFT-fisher, the two-branch ImageNet SIFT|LCS featurizer,
   newsgroups text) produces byte-identical fit/apply outputs under
   ``KEYSTONE_EXEC_WORKERS=4`` vs ``=0`` — the scheduler reorders only
   provably independent nodes.
2. Fault parity: an exception raised on a pool worker surfaces on the
   calling thread (it must not vanish into the pool), and a fit under
   the chaos fault plan stays bit-identical to the fault-free serial
   walk (every injected fault is recovered identically).
3. Scheduler semantics: structural duplicates execute ONCE (the second
   lands as a memo), fit-cache hits stay pruning leaves, independent
   host branches genuinely overlap, and a nested fit re-entering the
   executor from a pool thread takes the serial path (one bounded pool,
   no deadlock).
4. Profiler under concurrency: a 4-worker walk yields exact per-label
   call counts with non-overlapping wall attribution, rows carry the
   worker / queue-wait scheduling attrs, and ``trace_report --fit``
   renders the same table from the spans.
5. ``workers=0`` (the default) never constructs the parallel walk — the
   legacy serial path is byte-identical because it is the same code.
"""

import threading
import time

import numpy as np
import pytest

from keystone_tpu import native
from keystone_tpu.config import config
from keystone_tpu.workflow.executor import PipelineEnv, _ParallelWalk
from keystone_tpu.workflow.pipeline import Pipeline, Transformer

needs_native = pytest.mark.skipif(
    not native.available(), reason="native lib unavailable"
)


@pytest.fixture(autouse=True)
def _serial_default():
    """Every test starts and ends at the workers=0 default."""
    prior = config.exec_workers
    config.exec_workers = 0
    yield
    config.exec_workers = prior


def _fit_apply(pipe, X_test, workers):
    """One cold fit+apply under ``workers`` executor threads."""
    PipelineEnv.reset()
    config.exec_workers = workers
    try:
        out = np.asarray(pipe.fit().apply(X_test).get())
    finally:
        config.exec_workers = 0
        PipelineEnv.reset()
    return out


def _assert_walks_agree(pipe, X_test):
    serial = _fit_apply(pipe, X_test, 0)
    parallel = _fit_apply(pipe, X_test, 4)
    assert serial.dtype == parallel.dtype
    np.testing.assert_array_equal(serial, parallel)
    return serial


# ---------------------------------------------------------------------------
# Canonical-pipeline bit-identity (tiny scales)
# ---------------------------------------------------------------------------


def test_mnist_fft_bit_identical():
    from keystone_tpu.loaders import MnistLoader
    from keystone_tpu.pipelines.images.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_pipeline,
    )

    conf = MnistRandomFFTConfig(num_ffts=1, synthetic_n=256, seed=1)
    train, test = MnistLoader.synthetic(n=conf.synthetic_n, seed=conf.seed)
    pipe = build_pipeline(conf, train.data, train.labels)
    _assert_walks_agree(pipe, test.data[:64])


def test_cifar_random_patch_bit_identical():
    from keystone_tpu.loaders.cifar import CifarLoader
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.util import ClassLabelIndicators
    from keystone_tpu.pipelines.images.random_patch_cifar import (
        RandomPatchCifarConfig,
        build_featurizer,
    )

    train, test = CifarLoader.synthetic(n=96, seed=1)
    conf = RandomPatchCifarConfig(
        synthetic_n=96, num_filters=16, patch_sample=500, num_iters=1,
        lam=5.0,
    )
    feat = build_featurizer(conf, train.data)
    targets = ClassLabelIndicators(conf.num_classes)(train.labels)
    pipe = feat.and_then(
        BlockLeastSquaresEstimator(block_size=128, num_iters=1, lam=conf.lam),
        train.data,
        targets,
    )
    _assert_walks_agree(pipe, test.data[:16])


@needs_native
def test_voc_fisher_bit_identical():
    from keystone_tpu.loaders.voc import VOCLoader
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.pipelines.images.voc_sift_fisher import (
        VOCSIFTFisherConfig,
        build_featurizer,
    )

    train, test = VOCLoader.synthetic(n=32, num_classes=4)
    conf = VOCSIFTFisherConfig(
        pca_dims=8, gmm_k=2, gmm_iters=2, descriptor_sample=5000,
        sift_backend="native",  # the host-bound node the walk overlaps
    )
    feat = build_featurizer(conf, train.data)
    targets = (2.0 * train.labels - 1.0).astype(np.float32)
    pipe = feat.and_then(
        BlockLeastSquaresEstimator(block_size=64, num_iters=1, lam=1e-3),
        train.data,
        targets,
    )
    _assert_walks_agree(pipe, test.data[:8])


@needs_native
def test_imagenet_two_branch_featurizer_bit_identical():
    """THE motivating shape: the SIFT|LCS two-branch featurizer, whose
    independent host-bound branches the parallel walk overlaps."""
    from keystone_tpu.loaders.imagenet import ImageNetLoader
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
        build_featurizer,
        resolve_scale,
    )

    train, test = ImageNetLoader.synthetic(n=24, num_classes=4, size=32)
    conf = resolve_scale(ImageNetSiftLcsFVConfig(
        pca_dims=8, gmm_k=2, gmm_iters=2, descriptor_sample=5000,
        sift_backend="native",
    ))
    feat = build_featurizer(conf, train.data)
    _assert_walks_agree(feat, test.data[:8])


def test_newsgroups_text_bit_identical():
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

    train, test, classes = NewsgroupsDataLoader.synthetic(
        n=200, num_classes=3
    )
    featurizer = (
        Trim()
        .and_then(LowerCase())
        .and_then(Tokenizer())
        .and_then(NGramsFeaturizer(1, 2))
        .and_then(TermFrequency("log"))
        .and_then(CommonSparseFeatures(200), train.data)
    )
    pipe = featurizer.and_then(
        NaiveBayesEstimator(len(classes)), train.data, train.labels
    ).and_then(MaxClassifier())
    _assert_walks_agree(pipe, test.data[:32])


# ---------------------------------------------------------------------------
# Scheduler semantics
# ---------------------------------------------------------------------------


class HostWork(Transformer):
    """Deterministic non-jittable branch work that releases the GIL
    (numpy elementwise), so branches can genuinely overlap."""

    jittable = False

    def __init__(self, seed: int, iters: int = 40):
        self.seed = seed
        self.iters = iters

    def signature(self):
        return self.stable_signature(self.seed, self.iters)

    def apply_batch(self, X):
        Y = np.asarray(X, dtype=np.float32)
        for _ in range(self.iters):
            Y = np.tanh(Y + float(self.seed) * 1e-3)
        return Y


class Boom(Transformer):
    jittable = False

    def apply_batch(self, X):
        raise RuntimeError("injected worker fault")


def test_worker_fault_surfaces_on_caller(rng):
    """A fault on a pool thread cancels the schedule and re-raises on
    the calling thread — chaos parity with the serial walk."""
    X = rng.normal(size=(16, 8)).astype(np.float32)
    pipe = Pipeline.gather(
        [HostWork(1, iters=2).to_pipeline(), Boom().to_pipeline()]
    )
    config.exec_workers = 4
    with pytest.raises(RuntimeError, match="injected worker fault"):
        pipe.apply(X).get()
    # The session survives: the next walk runs normally.
    ok = Pipeline.gather(
        [HostWork(1, iters=2).to_pipeline(), HostWork(2, iters=2).to_pipeline()]
    )
    out = np.asarray(ok.apply(X).get())
    assert out.shape == (16, 16)


def test_chaos_fit_bit_identical_under_parallel_walk(rng):
    """The standard chaos plan (io:0.05,oom:1) injected while the
    parallel walk drives a fit: every fault recovers invisibly and the
    outputs match the fault-free serial walk bit for bit."""
    from keystone_tpu.nodes.stats.normalizer import L2Normalizer
    from keystone_tpu.nodes.stats.scalers import StandardScaler

    X = rng.normal(size=(128, 16)).astype(np.float32)
    pipe = StandardScaler().with_data(X).and_then(L2Normalizer())
    baseline = _fit_apply(pipe, X, 0)
    prior = (config.faults, config.faults_seed)
    try:
        config.faults, config.faults_seed = "io:0.05,oom:1", 0
        chaos = _fit_apply(pipe, X, 4)
    finally:
        config.faults, config.faults_seed = prior
    np.testing.assert_array_equal(baseline, chaos)


def test_structural_duplicates_execute_once(rng):
    """Two branches sharing one structural prefix: the parallel walk
    executes the prefix ONCE (hash ownership) and the duplicate lands as
    a memo — same dedup the serial loop's by_hash gives."""
    from keystone_tpu.utils.metrics import profile_scope, resource_profile

    from keystone_tpu.workflow.graph import Graph
    from keystone_tpu.workflow.operators import (
        DatasetOperator,
        TransformerOperator,
    )

    X = rng.normal(size=(32, 8)).astype(np.float32)
    # A raw graph (no optimizer dedup pass) holding two structurally
    # identical HostWork(7) nodes over one dataset — the duplicate shape
    # composition produces and the walk's by_hash memo must collapse.
    g = Graph()
    g, data = g.add(DatasetOperator(X), [])
    g, dup_a = g.add(TransformerOperator(HostWork(7, iters=2)), [data])
    g, dup_b = g.add(TransformerOperator(HostWork(7, iters=2)), [data])
    resource_profile.reset()
    config.exec_workers = 4
    try:
        with profile_scope():
            values = PipelineEnv.get().executor.execute_many(
                g, [dup_a, dup_b]
            )
        np.testing.assert_array_equal(
            np.asarray(values[dup_a]), np.asarray(values[dup_b])
        )
        row = next(
            r for r in resource_profile.rows() if r["node"] == "HostWork"
        )
        # The owner executes once; the duplicate lands as a memo — two
        # duplicates can never compute concurrently.
        assert row["calls"] == 2
        assert row["executed"] == 1
        assert row["cache_hits"] == 1
    finally:
        resource_profile.reset()
        config.exec_workers = 0


def test_fit_cache_hit_stays_a_pruning_leaf(rng):
    """A refit under the parallel walk serves the estimator from the
    session fit cache without re-executing its training subgraph."""
    from keystone_tpu.nodes.stats.scalers import StandardScaler
    from keystone_tpu.utils.metrics import profile_scope, resource_profile

    X = rng.normal(size=(64, 8)).astype(np.float32)
    pipe = StandardScaler().with_data(X)
    PipelineEnv.reset()
    config.exec_workers = 4
    try:
        pipe.fit()
        resource_profile.reset()
        with profile_scope():
            pipe.fit()
        rows = {r["node"]: r for r in resource_profile.rows()}
        fit_row = next(
            r for n, r in rows.items() if n.endswith(".fit")
        )
        assert fit_row["cache_hits"] == 1 and fit_row["executed"] == 0
        # The training Dataset node was pruned by the cache cut.
        assert "Dataset" not in rows
    finally:
        resource_profile.reset()
        config.exec_workers = 0
        PipelineEnv.reset()


def test_independent_host_branches_overlap(rng):
    """Two GIL-releasing host branches under 4 workers: their executor
    spans must overlap in time (the scheduler actually runs them
    concurrently, not merely out of order)."""
    from keystone_tpu.utils.metrics import active_tracer, reset_tracer

    X = rng.normal(size=(64, 512)).astype(np.float32)
    pipe = Pipeline.gather(
        [HostWork(1, iters=400).to_pipeline(),
         HostWork(2, iters=400).to_pipeline()]
    )
    prior_trace = config.trace
    config.trace = True
    reset_tracer()
    config.exec_workers = 4
    try:
        tracer = active_tracer()
        pipe.apply(X).get()
        spans = [
            s for s in tracer.spans()
            if s["name"] == "node:HostWork" and s["args"].get("cache") == "miss"
        ]
        assert len(spans) == 2
        (a, b) = sorted(spans, key=lambda s: s["start_ns"])
        assert b["start_ns"] < a["start_ns"] + a["dur_ns"], (
            "branches ran back to back — no overlap"
        )
        for s in spans:
            assert s["args"].get("worker", "").startswith("keystone-exec")
            assert s["args"].get("queue_wait_ms") is not None
    finally:
        config.trace = prior_trace
        config.exec_workers = 0
        reset_tracer()


def test_nested_fit_on_worker_takes_serial_path(rng):
    """An estimator whose fit() internally applies ANOTHER pipeline
    re-enters the executor from a pool thread: the nested walk must run
    serial (one bounded pool) and still produce the right answer."""
    from keystone_tpu.workflow.pipeline import Estimator

    class InnerApplyEstimator(Estimator):
        def signature(self):
            return ("inner-apply-est",)

        def fit(self, data):
            inner = Pipeline.gather(
                [HostWork(11, iters=2).to_pipeline(),
                 HostWork(12, iters=2).to_pipeline()]
            )
            feats = np.asarray(inner.apply(np.asarray(data)).get())
            mu = feats.mean(axis=0)[: np.asarray(data).shape[1]]

            class Center(Transformer):
                jittable = False

                def __init__(self, mu):
                    self.mu = mu

                def apply_batch(self, X):
                    return np.asarray(X) - self.mu

            return Center(mu)

    X = rng.normal(size=(32, 8)).astype(np.float32)
    pipe = InnerApplyEstimator().with_data(X)
    out_serial = _fit_apply(pipe, X, 0)
    out_parallel = _fit_apply(pipe, X, 4)
    np.testing.assert_array_equal(out_serial, out_parallel)


def test_workers_zero_never_builds_the_parallel_walk(rng, monkeypatch):
    """The default path is the LEGACY serial loop — same code, not a
    1-worker pool: _ParallelWalk must never be constructed."""
    from keystone_tpu.nodes.stats.normalizer import L2Normalizer
    from keystone_tpu.nodes.stats.scalers import StandardScaler

    def forbid(*a, **kw):
        raise AssertionError("parallel walk engaged at workers=0")

    monkeypatch.setattr(_ParallelWalk, "__init__", forbid)
    X = rng.normal(size=(32, 8)).astype(np.float32)
    assert config.exec_workers == 0
    out = np.asarray(
        StandardScaler().with_data(X).and_then(L2Normalizer())
        .fit().apply(X).get()
    )
    assert out.shape == X.shape


# ---------------------------------------------------------------------------
# Profiler under concurrency + trace_report --fit agreement
# ---------------------------------------------------------------------------


def test_profile_exact_counts_and_trace_report_agreement(rng, tmp_path):
    """A 4-worker profiled+traced walk: exact call counts per label,
    non-overlapping (per-execution) wall attribution, scheduling attrs
    populated — and `trace_report --fit` aggregates the executor spans
    into the SAME table."""
    import importlib
    import os
    import sys

    from keystone_tpu.utils.metrics import (
        active_tracer,
        profile_scope,
        reset_tracer,
        resource_profile,
    )

    X = rng.normal(size=(48, 16)).astype(np.float32)
    pipe = Pipeline.gather(
        [HostWork(1, iters=8).to_pipeline(),
         HostWork(2, iters=8).to_pipeline(),
         HostWork(3, iters=8).to_pipeline()]
    )
    prior_trace = config.trace
    config.trace = True
    reset_tracer()
    resource_profile.reset()
    config.exec_workers = 4
    try:
        tracer = active_tracer()
        with profile_scope():
            pipe.apply(X).get()
        rows = {r["node"]: r for r in resource_profile.rows()}
        # Exact attribution: 3 HostWork executions (one per branch seed —
        # distinct signatures, no dedup), 1 Gather, 1 Dataset.
        assert rows["HostWork"]["calls"] == 3
        assert rows["HostWork"]["executed"] == 3
        assert rows["Gather"]["calls"] == 1
        assert rows["Dataset"]["calls"] == 1
        for r in rows.values():
            if r["executed"]:
                assert r["wall_ms"] > 0
                assert r["queue_wait_ms"] is not None
                assert r["workers"], r
                for w in r["workers"]:
                    assert w.startswith("keystone-exec")
        # Per-label wall equals the sum of that label's span durations
        # (each execution attributed exactly once, no double counting).
        doc = tracer.export(str(tmp_path / "fit_trace.json"))
        sys.path.insert(
            0,
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "tools"),
        )
        try:
            trace_report = importlib.import_module("trace_report")
        finally:
            sys.path.pop(0)
        trows = {r["node"]: r for r in trace_report.fit_rows(doc)}
        assert set(trows) == set(rows)
        for label, tr in trows.items():
            assert tr["calls"] == rows[label]["calls"]
            assert tr["executed"] == rows[label]["executed"]
            assert tr["cache_hits"] == rows[label]["cache_hits"]
            assert tr["wall_ms"] == pytest.approx(
                rows[label]["wall_ms"], rel=0.05, abs=0.05
            )
        # Same renderer, same table shape for both sources.
        from keystone_tpu.utils.metrics import render_attribution_table

        live = render_attribution_table(resource_profile.rows())
        from_trace = render_attribution_table(trace_report.fit_rows(doc))
        assert [ln.split()[0] for ln in live.splitlines()[2:]] == [
            ln.split()[0] for ln in from_trace.splitlines()[2:]
        ]
    finally:
        config.trace = prior_trace
        config.exec_workers = 0
        reset_tracer()
        resource_profile.reset()


def test_bench_fit_harness_in_process():
    """`make bench-fit`'s harness at --quick scale: the row is
    well-formed, fingerprinted, and the bit-identity gate holds (the
    speedup gate is timing and belongs to the bench, not tier-1)."""
    import argparse
    import importlib
    import os
    import sys

    sys.path.insert(
        0,
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools"),
    )
    try:
        bench_fit = importlib.import_module("bench_fit")
    finally:
        sys.path.pop(0)
    args = argparse.Namespace(
        branches=2, workers=2, reps=1, rows=64, dim=32, classes=4,
        work_iters=4, quick=True, out=None,
    )
    row = bench_fit.run_bench(args)
    assert row["metric"] == "fit_parallel_walk"
    assert row["detail"]["bit_identical"] is True
    assert row["ok"] is True
    assert row["env"]["cpu_count"] == row["host_cores"]
    assert row["detail"]["serial_wall_s"] > 0
    assert row["detail"]["parallel_wall_s"] > 0


def test_dead_shared_pool_errors_instead_of_hanging(rng, monkeypatch):
    """A pool whose submit refuses (rebuilt/shut down under an active
    walk) must surface as the walk's error, not wedge run()'s drain wait
    with a phantom in-flight count."""
    from keystone_tpu.workflow import executor as executor_mod

    class DeadPool:
        def submit(self, fn, *a):
            raise RuntimeError("cannot schedule new futures after shutdown")

    monkeypatch.setattr(
        executor_mod, "_exec_pool", lambda workers: DeadPool()
    )
    X = rng.normal(size=(8, 4)).astype(np.float32)
    pipe = HostWork(1, iters=1).to_pipeline()
    config.exec_workers = 4
    with pytest.raises(RuntimeError, match="after shutdown"):
        pipe.apply(X).get()


def test_mark_delta_scopes_workers_to_the_window():
    """rows(since=mark) names only pool threads first seen AFTER the
    mark — pre-mark workers must not bleed into a phase's delta view."""
    from keystone_tpu.utils.metrics import ResourceProfile

    p = ResourceProfile()
    p.record_node("A", wall_ns=1000, worker="w0")
    p.record_node("A", wall_ns=1000, worker="w1")
    mark = p.mark()
    p.record_node("A", wall_ns=1000, worker="w2")
    (delta,) = p.rows(since=mark)
    assert delta["workers"] == ["w2"]
    (cumulative,) = p.rows()
    assert cumulative["workers"] == ["w0", "w1", "w2"]


def test_record_node_is_exact_under_concurrent_writers():
    """The ResourceProfile fold is one atomic read-modify-write: 4
    threads x 500 records keep exact totals."""
    from keystone_tpu.utils.metrics import ResourceProfile

    p = ResourceProfile()

    def pound(worker):
        for _ in range(500):
            p.record_node("N", wall_ns=1000, dispatch_ns=200,
                          queue_wait_ns=10, worker=worker)

    threads = [
        threading.Thread(target=pound, args=(f"w{i}",)) for i in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    (row,) = p.rows()
    assert row["calls"] == 2000
    assert row["wall_ms"] == pytest.approx(2.0)
    assert row["queue_wait_ms"] == pytest.approx(0.02)
    assert row["workers"] == ["w0", "w1", "w2", "w3"]
