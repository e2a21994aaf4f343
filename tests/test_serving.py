"""Shape-stable serving tests: bucket ladder math, padded-vs-unpadded
bit-equivalence, zero post-warmup compiles over a mixed-size trace,
row-dependence refusal, batch_call wiring, and the micro-batcher.

The compile-count assertions are backed two ways: the serving layer's own
counters AND a jax.monitoring listener on XLA compile-cache requests (one
event per backend compile), so a silent recompile on the hot path cannot
hide.
"""

import threading

import jax
import numpy as np
import pytest

from keystone_tpu.config import config, pow2_ladder
from keystone_tpu.nodes.learning.linear_mapper import LinearMapper
from keystone_tpu.nodes.stats.hellinger import SignedHellingerMapper
from keystone_tpu.nodes.stats.normalizer import L2Normalizer
from keystone_tpu.nodes.stats.random_features import CosineRandomFeatures
from keystone_tpu.nodes.stats.scalers import StandardScaler, StandardScalerModel
from keystone_tpu.utils.metrics import CompileEventCounter, serving_counters
from keystone_tpu.workflow import (
    CompiledPipeline,
    PipelineService,
    RowDependenceError,
    Transformer,
)
from keystone_tpu.workflow.pipeline import FusedTransformer
from keystone_tpu.workflow.serving import (
    bucket_for,
    bucketed_call,
    resolve_ladder,
)


@pytest.fixture(autouse=True)
def serve_config():
    """Isolate the process-wide serving knobs and counters per test."""
    prior = (config.serve_buckets, config.serve_max_batch)
    serving_counters.reset()
    yield
    config.serve_buckets, config.serve_max_batch = prior
    serving_counters.reset()


# The compile oracle shared with tools/bench_serve.py — one listener per
# process (registration is global and permanent).
_compile_events = CompileEventCounter()


def _head(d=8, D=16, k=3, seed=0):
    """A canonical fused serving head (the TIMIT/CIFAR-style apply tail)."""
    rng = np.random.default_rng(seed)
    return FusedTransformer(
        [
            StandardScalerModel(
                rng.normal(size=d).astype(np.float32),
                (1.0 + rng.uniform(size=d)).astype(np.float32),
            ),
            CosineRandomFeatures.create(d, D, seed=seed),
            SignedHellingerMapper(),
            L2Normalizer(),
            LinearMapper(rng.normal(size=(D, k)).astype(np.float32)),
        ]
    )


class RowMean(Transformer):
    """Batch output depends on other rows: padding must be refused."""

    row_independent = False

    def apply_batch(self, X):
        return X - X.mean(axis=0)


# ---------------------------------------------------------------------------
# Ladder math
# ---------------------------------------------------------------------------


def test_pow2_ladder():
    assert pow2_ladder(8) == (1, 2, 4, 8)
    assert pow2_ladder(1) == (1,)
    # Non-pow2 top: the max batch itself always serves as the top bucket.
    assert pow2_ladder(100) == (1, 2, 4, 8, 16, 32, 64, 100)
    with pytest.raises(ValueError):
        pow2_ladder(0)


def test_bucket_for_boundaries():
    ladder = (1, 2, 4, 8)
    assert bucket_for(1, ladder) == 1
    assert bucket_for(3, ladder) == 4
    assert bucket_for(8, ladder) == 8
    assert bucket_for(9, ladder) is None  # oversize: caller chunks


def test_resolve_ladder_precedence():
    config.serve_buckets = (4, 16)
    assert resolve_ladder() == (4, 16)
    assert resolve_ladder(buckets=(2, 8)) == (2, 8)
    config.serve_buckets = ()
    config.serve_max_batch = 8
    assert resolve_ladder() == (1, 2, 4, 8)
    # An explicit max extends/clips the explicit ladder.
    assert resolve_ladder(buckets=(2, 64), max_batch=8) == (2, 8)


# ---------------------------------------------------------------------------
# CompiledPipeline: equivalence + compile discipline
# ---------------------------------------------------------------------------


def test_padded_bit_equivalence_canonical_chains(rng):
    """Mask-safety, at the bit level: at a FIXED bucket shape, the pad
    rows must be provably inert — real rows come out bit-identical no
    matter what the padding contains (last-row replication, zeros, or
    garbage). This is the property that makes bucket-padding sound; it
    holds exactly, unlike cross-batch-size comparisons where CPU gemm
    vectorization can differ in the last ulp."""
    d = 8
    chains = [
        _head(d=d),
        FusedTransformer([SignedHellingerMapper(), L2Normalizer()]),
        FusedTransformer(
            [
                CosineRandomFeatures.create(d, 12, seed=3),
                LinearMapper(rng.normal(size=(12, 2)).astype(np.float32)),
            ]
        ),
    ]
    for chain in chains:
        cp = CompiledPipeline(chain, max_batch=32).warmup((d,))
        jitted = jax.jit(chain.apply_batch)
        for n in (1, 3, 5, 9, 17, 31):
            X = rng.normal(size=(n, d)).astype(np.float32)
            b = bucket_for(n, cp.ladder)
            pads = [
                np.broadcast_to(X[-1:], (b - n, d)),
                np.zeros((b - n, d), np.float32),
                rng.normal(size=(b - n, d)).astype(np.float32) * 100,
            ]
            outs = [
                np.asarray(jitted(np.concatenate([X, p])))[:n] for p in pads
            ]
            assert np.array_equal(outs[0], outs[1])
            assert np.array_equal(outs[0], outs[2])
            # The serving engine returns exactly the fixed-shape program's
            # real rows...
            assert np.array_equal(cp(X), outs[0])
            # ...and matches the per-shape jit at the exact size to float
            # tolerance (bit-equal is not guaranteed across gemm shapes).
            np.testing.assert_allclose(
                cp(X), np.asarray(jitted(X)), rtol=2e-6, atol=2e-6
            )


def test_zero_compiles_after_warmup_on_mixed_trace(rng):
    """A warmed CompiledPipeline performs ZERO new XLA compiles over a
    50-request mixed-size trace (the acceptance gate), measured at the
    monitoring layer, the serving counters, and the engine's own count."""
    d = 8
    cp = CompiledPipeline(_head(d=d), max_batch=32).warmup((d,))
    warm_compiles = cp.compile_count
    # One ladder per replica: warmup covers the whole pool.
    assert warm_compiles == len(cp.ladder) * len(cp.replicas)
    ev0 = _compile_events.count
    c0 = serving_counters.snapshot()["compiles"]
    sizes = rng.integers(1, 33, size=50)
    for n in sizes:
        out = cp(rng.normal(size=(int(n), d)).astype(np.float32))
        assert out.shape == (int(n), 3)
    assert cp.compile_count == warm_compiles
    assert serving_counters.snapshot()["compiles"] == c0
    assert _compile_events.count == ev0
    hits = serving_counters.snapshot()["bucket_hits"]
    assert sum(hits.values()) == 50
    assert set(hits) <= set(cp.ladder)


def test_warmup_idempotent_and_cold_bucket_counted(rng):
    d = 4
    cp = CompiledPipeline(_head(d=d), max_batch=8)
    cp.warmup((d,))
    n = cp.compile_count
    cp.warmup((d,))  # no-op: every bucket already compiled
    assert cp.compile_count == n

    # A never-warmed engine warms the whole ladder (on every replica) off
    # the first request's signature (correct, but first-traffic latency
    # pays the ladder).
    cold = CompiledPipeline(_head(d=d, seed=1), max_batch=8)
    cold(rng.normal(size=(3, d)).astype(np.float32))
    assert cold.compile_count == len(cold.ladder) * len(cold.replicas)

    # Re-warming a shape-polymorphic chain for a NEW traffic signature
    # drops the stale executables and recompiles the ladder.
    poly = CompiledPipeline(
        FusedTransformer([SignedHellingerMapper(), L2Normalizer()]),
        max_batch=8,
    ).warmup((d,))
    n_poly = poly.compile_count
    poly.warmup((d + 2,))
    assert poly.compile_count == 2 * n_poly
    out = poly(np.ones((3, d + 2), np.float32))
    assert out.shape == (3, d + 2)


def test_oversize_batch_chunks_through_top_bucket(rng):
    d = 4
    cp = CompiledPipeline(_head(d=d), max_batch=8).warmup((d,))
    X = rng.normal(size=(21, d)).astype(np.float32)
    out = cp(X)
    assert out.shape == (21, 3)
    oracle = jax.jit(cp.transformer.apply_batch)
    np.testing.assert_allclose(
        out, np.asarray(oracle(X)), rtol=1e-6, atol=1e-6
    )


def test_feature_shape_mismatch_and_empty_batch(rng):
    d = 4
    cp = CompiledPipeline(_head(d=d), max_batch=8).warmup((d,))
    with pytest.raises(ValueError, match="feature shape"):
        cp(rng.normal(size=(3, d + 1)).astype(np.float32))
    with pytest.raises(ValueError, match="empty"):
        cp(np.zeros((0, d), np.float32))


def test_compiled_pipeline_from_fitted_estimator_pipeline(rng):
    """Pipeline.compiled() fits estimators, fuses the chain, and serves
    numerically-identical results to graph execution."""
    d = 6
    Xtrain = rng.normal(size=(32, d)).astype(np.float32)
    pipe = StandardScaler().with_data(Xtrain).and_then(L2Normalizer())
    cp = pipe.compiled(max_batch=16).warmup((d,))
    X = rng.normal(size=(5, d)).astype(np.float32)
    np.testing.assert_allclose(
        cp(X), np.asarray(pipe(X).get()), rtol=1e-6, atol=1e-6
    )


def test_serving_refuses_host_chains_and_compiles_gather_joins(rng):
    from keystone_tpu.workflow import Pipeline

    class HostOp(Transformer):
        jittable = False

        def apply_batch(self, X):
            return X

    with pytest.raises(TypeError, match="jittable"):
        CompiledPipeline(HostOp())
    with pytest.raises(TypeError, match="jittable"):
        Pipeline.gather([L2Normalizer(), HostOp()]).compiled()
    # A gather join of jittable branches is one program: the served rows
    # equal the un-compiled pipeline's, tail stage included.
    gathered = Pipeline.gather(
        [L2Normalizer(), SignedHellingerMapper().and_then(L2Normalizer())]
    ).and_then(SignedHellingerMapper())
    X = rng.normal(size=(5, 6)).astype(np.float32)
    served = gathered.compiled(buckets=(8,), devices=1)(X)
    np.testing.assert_array_equal(served, np.asarray(gathered(X).get()))


# ---------------------------------------------------------------------------
# Row dependence
# ---------------------------------------------------------------------------


def test_row_dependent_refused_on_compiled_path():
    with pytest.raises(RowDependenceError, match="RowMean"):
        CompiledPipeline(RowMean())
    with pytest.raises(RowDependenceError, match="RowMean"):
        CompiledPipeline(FusedTransformer([L2Normalizer(), RowMean()]))


def test_row_dependent_falls_back_on_bucketed_batch_call(rng, caplog):
    """The process-wide knob must never crash a working pipeline: a
    row-coupled transformer is served per-shape (padding refused) with a
    one-time warning instead."""
    import logging

    from keystone_tpu.workflow import serving

    serving._fallback_warned.clear()
    config.serve_buckets = (4, 8)
    t = RowMean()
    X = rng.normal(size=(3, 4)).astype(np.float32)
    with caplog.at_level(logging.WARNING, logger="keystone_tpu"):
        got = np.asarray(t.batch_call(X))
    np.testing.assert_allclose(got, X - X.mean(axis=0), rtol=1e-6, atol=1e-6)
    assert any("RowMean" in r.message for r in caplog.records)
    # No padded/bucketed call was recorded for it.
    assert serving_counters.snapshot()["calls"] == 0


def test_row_dependence_flags_on_patch_nodes():
    from keystone_tpu.nodes.images.patches import (
        CenterCornerPatcher,
        RandomPatcher,
        Windower,
    )

    assert not Windower(1, 2).row_independent
    assert not CenterCornerPatcher(2).row_independent
    assert not RandomPatcher(4, 2).row_independent
    assert L2Normalizer().row_independent
    fused = FusedTransformer([L2Normalizer(), Windower(1, 2)])
    assert not fused.row_independent


# ---------------------------------------------------------------------------
# batch_call wiring (config.serve_buckets)
# ---------------------------------------------------------------------------


def test_batch_call_bucketing_matches_pershape_jit(rng):
    d = 8
    chain = _head(d=d)
    oracle = jax.jit(_head(d=d).apply_batch)  # fresh twin, per-shape jit
    config.serve_buckets = (4, 8, 16)
    for n in (1, 3, 6, 13, 16):
        X = rng.normal(size=(n, d)).astype(np.float32)
        got = np.asarray(chain.batch_call(X))
        np.testing.assert_allclose(
            got, np.asarray(oracle(X)), rtol=2e-6, atol=2e-6
        )
    # The jit cache is bounded by the ladder, not the request mix.
    from keystone_tpu.workflow.serving import _jit_cache_size

    assert _jit_cache_size(chain._jitted()) <= 3


def test_batch_call_bucketing_oversize_chunks(rng):
    d = 4
    chain = FusedTransformer([SignedHellingerMapper(), L2Normalizer()])
    config.serve_buckets = (4,)
    X = rng.normal(size=(11, d)).astype(np.float32)
    got = np.asarray(chain.batch_call(X))
    ref = np.asarray(jax.jit(chain.apply_batch)(X))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
    assert got.shape == ref.shape


def test_batch_call_disabled_ladder_is_pershape(rng):
    config.serve_buckets = ()
    chain = FusedTransformer([SignedHellingerMapper(), L2Normalizer()])
    for n in (3, 5):
        chain.batch_call(rng.normal(size=(n, 4)).astype(np.float32))
    assert serving_counters.snapshot()["calls"] == 0  # bucketing untouched


# ---------------------------------------------------------------------------
# PipelineService micro-batcher
# ---------------------------------------------------------------------------


def test_service_coalesces_and_matches_direct(rng):
    d = 8
    cp = CompiledPipeline(_head(d=d), max_batch=32).warmup((d,))
    rows = [rng.normal(size=(d,)).astype(np.float32) for _ in range(12)]
    batch = rng.normal(size=(5, d)).astype(np.float32)
    with PipelineService(cp, max_delay_ms=20.0) as svc:
        futs = [svc.submit(r) for r in rows]
        bfut = svc.submit(batch)
        outs = [f.result(timeout=30) for f in futs]
        bout = bfut.result(timeout=30)
    for r, o in zip(rows, outs):
        assert o.shape == (3,)
        # Coalescing serves the row inside a larger bucket: identical to a
        # solo call up to gemm-shape vectorization (last-ulp) differences.
        np.testing.assert_allclose(o, cp(r[None])[0], rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(bout, cp(batch), rtol=2e-6, atol=2e-6)
    stats = svc.stats()
    assert stats["requests"] == 13
    assert stats["rows_served"] == 17
    assert 1 <= stats["batches_run"] <= 13


def test_service_concurrent_clients(rng):
    d = 4
    cp = CompiledPipeline(_head(d=d), max_batch=16).warmup((d,))
    results, lock = {}, threading.Lock()

    def client(cid):
        crng = np.random.default_rng(cid)
        x = crng.normal(size=(d,)).astype(np.float32)
        out = svc.submit(x).result(timeout=30)
        with lock:
            results[cid] = (x, out)

    with PipelineService(cp, max_delay_ms=5.0) as svc:
        threads = [
            threading.Thread(target=client, args=(c,)) for c in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert len(results) == 8
    for x, out in results.values():
        np.testing.assert_allclose(out, cp(x[None])[0], rtol=2e-6, atol=2e-6)


def test_service_requires_warmup_and_rejects_after_close(rng):
    d = 4
    cold = CompiledPipeline(_head(d=d), max_batch=8)
    with pytest.raises(RuntimeError, match="warm"):
        PipelineService(cold)
    svc = PipelineService(cold.warmup((d,)))
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(np.zeros(d, np.float32))


def test_service_shape_mismatch_raises_at_submit(rng):
    d = 4
    cp = CompiledPipeline(_head(d=d), max_batch=8).warmup((d,))
    with PipelineService(cp) as svc:
        with pytest.raises(ValueError, match="shape"):
            svc.submit(np.zeros((2, d + 1), np.float32))


# ---------------------------------------------------------------------------
# Satellites: metrics + cache memo
# ---------------------------------------------------------------------------


def test_achieved_tflops_compiles_once():
    from keystone_tpu.utils.metrics import achieved_tflops

    W = np.ones((8, 8), np.float32)
    ev0 = _compile_events.count
    out = achieved_tflops(lambda x: x @ W, np.ones((4, 8), np.float32))
    assert _compile_events.count - ev0 == 1  # one lowered/compiled object
    assert out["flops"] > 0
    assert out["seconds"] > 0


def test_flops_ratio_memo_fifo_bounded():
    from keystone_tpu.workflow import cache as wcache

    wcache._flops_ratio_memo.clear()
    for i in range(wcache._FLOPS_MEMO_CAP):
        wcache._flops_ratio_memo[("sentinel", i)] = 1.0
    t = L2Normalizer()
    ratio = wcache.Profiler._flops_ratio(
        t, np.ones((4, 4), np.float32), 8.0
    )
    assert ratio is not None
    assert len(wcache._flops_ratio_memo) <= wcache._FLOPS_MEMO_CAP
    # FIFO: the oldest sentinel went first, the fresh key is present.
    assert ("sentinel", 0) not in wcache._flops_ratio_memo
    assert ("sentinel", 1) in wcache._flops_ratio_memo
