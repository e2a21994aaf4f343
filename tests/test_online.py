"""Online learning subsystem (workflow/online.py, ISSUE-15).

The equivalence contracts, pinned:

- **Grouping invariance**: ``partial_fit`` over K batches is
  BIT-identical to one ``partial_fit`` over their concatenation — the
  buffered fixed-phase chunk fold makes the batching of the stream
  unobservable — and folds of sharded device batches are bit-identical
  to folds of the same bytes on the host (the RowMatrix re-shard
  placement-invariance rule).
- **Batch agreement**: the online re-solve (uncentered sums + exact
  rank-one centering correction) matches the classic centered batch
  ``fit`` numerically (not bitwise — documented).
- **Decay / window math** pinned against NumPy float64 oracles
  (exponentially-weighted resp. last-k-batches ridge, intercepts
  included), plus subtract-on-evict consistency and the
  ``windows_evicted`` counter.
- **Typed refusals**: width/label-tail/mesh-manifest mismatches raise
  ``OnlineStateError``; a checkpoint resumed under a different mesh
  width raises the shared ``MeshMismatchError``.
- **Continuous refresh**: the OnlineTrainer folds, re-solves, publishes
  versioned artifacts, and hot-swaps a live daemon; a refresh killed at
  the ``refresh_abort``/``swap_abort`` fault sites leaves the old
  generation serving and the retained state (and its checkpoint)
  resuming bit-identically. A/B-serving answers two generations from
  one replica pool by per-tenant routing.

These tests must pass identically under ``make chaos``
(io:0.05,oom:1,conn_drop:0.05): daemon clients retry dropped
connections, and the fold/checkpoint paths carry no chaos fault sites.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

from keystone_tpu.config import config
from keystone_tpu.nodes.learning.block_least_squares import (
    BlockLeastSquaresEstimator,
    BlockWeightedLeastSquaresEstimator,
)
from keystone_tpu.nodes.learning.least_squares import LeastSquaresEstimator
from keystone_tpu.nodes.learning.linear_mapper import (
    LinearMapEstimator,
    LinearMapper,
)
from keystone_tpu.nodes.stats.normalizer import L2Normalizer
from keystone_tpu.nodes.stats.random_features import CosineRandomFeatures
from keystone_tpu.utils import reliability
from keystone_tpu.utils.metrics import metrics_registry, online_counters
from keystone_tpu.utils.mesh import MeshMismatchError, default_mesh
from keystone_tpu.utils.reliability import RefreshAborted
from keystone_tpu.workflow import LabelEstimator
from keystone_tpu.workflow.online import (
    OnlineState,
    OnlineStateError,
    OnlineTrainer,
    supports_partial_fit,
)
from keystone_tpu.workflow.serialization import save_artifact

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")

D_IN, K = 10, 3


@pytest.fixture
def faults():
    """Arm a fault plan for the test; restores the prior plan after
    (the test_daemon fixture pattern)."""
    prior = (config.faults, config.faults_seed)

    def arm(spec: str, seed: int = 0):
        config.faults, config.faults_seed = spec, seed
        reliability.reset_fault_plan()

    yield arm
    config.faults, config.faults_seed = prior
    reliability.reset_fault_plan()


def _data(n=300, d=D_IN, k=K, seed=0, noise=0.01):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    Wt = rng.normal(size=(d, k)).astype(np.float32)
    Y = (X @ Wt + noise * rng.normal(size=(n, k))).astype(np.float32)
    return X, Y


def _split(X, Y, cuts):
    edges = [0] + list(cuts) + [len(X)]
    return [(X[a:b], Y[a:b]) for a, b in zip(edges[:-1], edges[1:])]


# ---------------------------------------------------------------------------
# The fold contracts
# ---------------------------------------------------------------------------


def test_partial_fit_k_batches_bit_identical_to_concat():
    """The tentpole contract: the batching of the stream must be
    unobservable in the bits — awkward batch sizes straddle the
    canonical chunk boundary on purpose."""
    X, Y = _data()
    est = LinearMapEstimator(lam=1e-3)
    st_k = None
    for bx, by in _split(X, Y, [37, 110, 111, 230]):
        st_k = est.partial_fit(bx, by, state=st_k)
    st_1 = est.partial_fit(X, Y)
    m_k, m_1 = est.solve_online(st_k), est.solve_online(st_1)
    assert np.array_equal(np.asarray(m_k.W), np.asarray(m_1.W))
    assert np.array_equal(np.asarray(m_k.b), np.asarray(m_1.b))
    # ... and a THIRD grouping agrees too.
    st_3 = None
    for bx, by in _split(X, Y, [1, 2, 299]):
        st_3 = est.partial_fit(bx, by, state=st_3)
    m_3 = est.solve_online(st_3)
    assert np.array_equal(np.asarray(m_3.W), np.asarray(m_1.W))


def test_partial_fit_sharded_fold_bit_identical():
    """Sharded arrival placement must be unobservable: every fold
    re-shards through RowMatrix onto the one mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    X, Y = _data(n=296)  # divisible by the 8-device mesh
    est = LinearMapEstimator(lam=1e-3)
    mesh = default_mesh()
    Xs = jax.device_put(X, NamedSharding(mesh, P(config.data_axis)))
    Ys = jax.device_put(Y, NamedSharding(mesh, P(config.data_axis)))
    m_sharded = est.solve_online(est.partial_fit(Xs, Ys))
    m_host = est.solve_online(est.partial_fit(X, Y))
    assert np.array_equal(np.asarray(m_sharded.W), np.asarray(m_host.W))
    assert np.array_equal(np.asarray(m_sharded.b), np.asarray(m_host.b))


def test_online_solve_matches_batch_fit_numerically():
    """The online solve is the SAME math as the centered batch fit at a
    different (exact-correction) flop grouping: predictions agree to
    f32 working precision, intercept included."""
    X, Y = _data()
    est = LinearMapEstimator(lam=1e-3)
    online = est.solve_online(est.partial_fit(X, Y))
    batch = est.fit(X, Y)
    po = np.asarray(online.apply_batch(X[:64]))
    pb = np.asarray(batch.apply_batch(X[:64]))
    scale = max(np.abs(pb).max(), 1.0)
    assert np.allclose(po, pb, atol=1e-4 * scale)


def test_intercept_means_ride_the_fold():
    """The retained column sums ARE the intercept means: exact to f64
    addition over the canonical chunks."""
    X, Y = _data()
    st = LinearMapEstimator().partial_fit(X, Y)
    g, ab, xs, ys, n = st._totals_with_pending()
    assert n == len(X)
    assert np.allclose(xs / n, X.astype(np.float64).mean(axis=0),
                       atol=1e-6)
    assert np.allclose(ys / n, Y.astype(np.float64).mean(axis=0),
                       atol=1e-6)


def test_decay_matches_numpy_oracle():
    """γ-decay per fold = exponentially-weighted ridge: pinned against a
    float64 weighted-normal-equations oracle, intercept included."""
    X, Y = _data()
    est = LinearMapEstimator(lam=1e-3)
    gamma = 0.5
    st = None
    batches = _split(X, Y, [100, 200])
    for bx, by in batches:
        st = est.partial_fit(bx, by, state=st, decay=gamma)
    m = est.solve_online(st)
    w = np.concatenate([
        np.full(len(b[0]), gamma ** (len(batches) - 1 - i))
        for i, b in enumerate(batches)
    ])
    Xd, Yd = X.astype(np.float64), Y.astype(np.float64)
    ne = w.sum()
    xm, ym = (w @ Xd) / ne, (w @ Yd) / ne
    Xc, Yc = Xd - xm, Yd - ym
    G = (Xc * w[:, None]).T @ Xc + 1e-3 * np.eye(D_IN)
    Wo = np.linalg.solve(G, (Xc * w[:, None]).T @ Yc)
    assert np.allclose(np.asarray(m.W), Wo, atol=2e-3)
    assert np.allclose(np.asarray(m.b), ym - xm @ Wo, atol=2e-3)


def test_window_matches_oracle_and_counts_evictions():
    """window=k keeps exactly the last k calls: the running totals match
    a fresh fold of the live windows (subtract-on-evict is benign in
    f64) and the solve matches the last-k NumPy oracle."""
    X, Y = _data()
    est = LinearMapEstimator(lam=1e-3)
    before = online_counters.get("windows_evicted")
    st = None
    batches = _split(X, Y, [100, 200])
    for bx, by in batches:
        st = est.partial_fit(bx, by, state=st, window=2)
    assert online_counters.get("windows_evicted") == before + 1
    # Totals == a fresh state folded with only the live windows.
    fresh = None
    for bx, by in batches[1:]:
        fresh = est.partial_fit(bx, by, state=fresh, window=2)
    for a, b in zip(st._totals_with_pending(),
                    fresh._totals_with_pending()):
        assert np.allclose(a, b, rtol=1e-9, atol=1e-9)
    # ... and the solve matches the last-200-rows oracle.
    m = est.solve_online(st)
    Xd = X[100:].astype(np.float64)
    Yd = Y[100:].astype(np.float64)
    xm, ym = Xd.mean(axis=0), Yd.mean(axis=0)
    Xc, Yc = Xd - xm, Yd - ym
    Wo = np.linalg.solve(Xc.T @ Xc + 1e-3 * np.eye(D_IN), Xc.T @ Yc)
    assert np.allclose(np.asarray(m.W), Wo, atol=2e-3)


def test_fold_copies_caller_buffers():
    """A streaming reader reusing ONE preallocated batch buffer must not
    corrupt pending rows: the fold copies what it buffers."""
    X, Y = _data(n=120)
    est = LinearMapEstimator(lam=1e-3)
    buf_x = np.empty((40, D_IN), np.float32)
    buf_y = np.empty((40, K), np.float32)
    st = None
    for a in (0, 40, 80):
        buf_x[:] = X[a:a + 40]
        buf_y[:] = Y[a:a + 40]
        st = est.partial_fit(buf_x, buf_y, state=st)
        buf_x[:] = np.nan  # the reader clobbers its buffer
        buf_y[:] = np.nan
    m = est.solve_online(st)
    m_ref = est.solve_online(est.partial_fit(X, Y))
    assert np.array_equal(np.asarray(m.W), np.asarray(m_ref.W))


def test_typed_refusals():
    X, Y = _data(n=64)
    est = LinearMapEstimator()
    st = est.partial_fit(X, Y)
    with pytest.raises(OnlineStateError, match="width"):
        st.fold(np.zeros((4, D_IN + 1), np.float32), Y[:4])
    with pytest.raises(OnlineStateError, match="label tail"):
        st.fold(X[:4], np.zeros((4, K + 2), np.float32))
    with pytest.raises(OnlineStateError, match="row mismatch"):
        st.fold(X[:4], Y[:5])
    with pytest.raises(OnlineStateError, match="empty"):
        st.fold(X[:0], Y[:0])
    with pytest.raises(OnlineStateError, match="exclusive"):
        st.decay(0.5) if st.window else OnlineState(
            D_IN, (K,), window=2
        ).decay(0.5)
    with pytest.raises(OnlineStateError, match="empty online state"):
        OnlineState(D_IN, (K,)).solve()
    with pytest.raises(OnlineStateError, match="label tail"):
        # ndim>=2 tails would break the rank-one intercept centering in
        # solve(): refused at creation, not a crash later.
        OnlineState.for_batch(X, np.zeros((64, K, 2), np.float32))
    with pytest.raises(OnlineStateError, match="chunk_rows"):
        # Fold granularity is fingerprint identity: a conflicting
        # chunk_rows on a later call refuses like a conflicting window.
        est.partial_fit(X[:4], Y[:4], state=est.partial_fit(X, Y),
                        chunk_rows=64)
    with pytest.raises(OnlineStateError, match="mesh"):
        st.device_count = 99
        st.fold(X[:4], Y[:4])


def test_mesh_manifest_refusal_on_resume(tmp_path, monkeypatch):
    """With elastic migration pinned off (KEYSTONE_ELASTIC_MESH=0), a
    snapshot recorded under one mesh width refuses to resume under
    another — the shared MeshMismatchError, never a wrong-answer
    resume; a different-problem snapshot refuses typed too. The
    default-on migration path is pinned in test_elastic_mesh.py."""
    from keystone_tpu.config import config

    monkeypatch.setattr(config, "elastic_mesh", False)
    X, Y = _data(n=64)
    st = LinearMapEstimator().partial_fit(X, Y)
    st.save(str(tmp_path))
    # Doctor the saved manifest: folded on a 2-device mesh.
    from keystone_tpu.workflow.disk_cache import DiskCache

    store = DiskCache(str(tmp_path), suffix=".online.pkl")
    snap = store.get("online_state")
    snap["fingerprint"]["device_count"] = 2
    store.put("online_state", snap, overwrite=True)
    with pytest.raises(MeshMismatchError, match="mesh"):
        OnlineState.load(str(tmp_path))
    # A different dtype REGIME (same mesh) is an OnlineStateError, not a
    # mesh one — the accumulators carry a dtype identity.
    snap["fingerprint"]["device_count"] = st.device_count
    snap["fingerprint"]["default_dtype"] = "float64"
    store.put("online_state", snap, overwrite=True)
    with pytest.raises(OnlineStateError, match="different problem"):
        OnlineState.load(str(tmp_path))


def test_checkpoint_resume_bit_identical(tmp_path):
    """Kill-and-resume mid-stream: the reloaded state (accumulators AND
    the pending partial-chunk buffer) continues to the same bits as the
    uninterrupted fold."""
    X, Y = _data()
    est = LinearMapEstimator(lam=1e-3)
    batches = _split(X, Y, [70, 140, 210])
    st = None
    for bx, by in batches[:2]:
        st = est.partial_fit(bx, by, state=st)
    st.save(str(tmp_path))
    resumed = OnlineState.load(str(tmp_path))  # "new process"
    assert resumed is not None and resumed.folds == 2
    for bx, by in batches[2:]:
        resumed = est.partial_fit(bx, by, state=resumed)
    uninterrupted = est.partial_fit(X, Y)
    m_r = est.solve_online(resumed)
    m_u = est.solve_online(uninterrupted)
    assert np.array_equal(np.asarray(m_r.W), np.asarray(m_u.W))
    assert np.array_equal(np.asarray(m_r.b), np.asarray(m_u.b))


# ---------------------------------------------------------------------------
# The estimator family
# ---------------------------------------------------------------------------


def test_block_least_squares_partial_fit():
    X, Y = _data()
    est = BlockLeastSquaresEstimator(lam=1e-3)
    m = est.solve_online(est.partial_fit(X, Y))
    # Same exact solve as the LinearMap head, in BlockLinearMapper garb.
    ref = LinearMapEstimator(lam=1e-3)
    m_ref = ref.solve_online(ref.partial_fit(X, Y))
    assert np.array_equal(np.asarray(m.W), np.asarray(m_ref.W))
    assert np.array_equal(np.asarray(m.b), np.asarray(m_ref.b))
    assert m.blocks == ((0, D_IN),)
    # fit_intercept=False drops the correction AND the bias.
    est0 = BlockLeastSquaresEstimator(lam=1e-3, fit_intercept=False)
    m0 = est0.solve_online(est0.partial_fit(X, Y))
    assert m0.b is None
    Xd, Yd = X.astype(np.float64), Y.astype(np.float64)
    Wo = np.linalg.solve(Xd.T @ Xd + 1e-3 * np.eye(D_IN), Xd.T @ Yd)
    assert np.allclose(np.asarray(m0.W), Wo, atol=2e-3)


def test_least_squares_estimator_partial_fit_and_support_map():
    X, Y = _data(n=128)
    est = LeastSquaresEstimator(lam=1e-3)
    m = est.solve_online(est.partial_fit(X, Y))
    assert isinstance(m, LinearMapper)
    assert est.last_choice is not None and est.last_choice.name == "normal"
    assert supports_partial_fit(LinearMapEstimator())
    assert supports_partial_fit(BlockLeastSquaresEstimator())
    assert supports_partial_fit(LeastSquaresEstimator())
    # Class-rebalanced weights need full class counts: contract nulled.
    assert not supports_partial_fit(BlockWeightedLeastSquaresEstimator())


def test_online_counters_visible_on_registry():
    before = online_counters.get("batches_folded")
    est = LinearMapEstimator()
    X, Y = _data(n=32)
    est.solve_online(est.partial_fit(X, Y))
    snap = metrics_registry.snapshot()["online"]
    assert snap["batches_folded"] >= before + 1
    assert snap["resolves"] >= 1
    assert "keystone_online" in metrics_registry.prometheus()


def test_one_d_labels_fold_and_solve():
    """The CSV label_col shape: 1-D labels ride the same fold (AᵀB is
    (d,), the intercept a scalar)."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(100, 6)).astype(np.float32)
    y = (X @ rng.normal(size=6).astype(np.float32) + 0.5).astype(np.float32)
    est = LinearMapEstimator(lam=1e-3)
    st = None
    for a, b in ((0, 33), (33, 100)):
        st = est.partial_fit(X[a:b], y[a:b], state=st)
    m = est.solve_online(st)
    m1 = est.solve_online(est.partial_fit(X, y))
    assert np.array_equal(np.asarray(m.W), np.asarray(m1.W))
    pred = np.asarray(m.apply_batch(X))
    assert np.allclose(pred, y, atol=0.05)


# ---------------------------------------------------------------------------
# Pipeline.refit_stream
# ---------------------------------------------------------------------------


def _drift_pipeline(X, Y, lam=1e-3, head=None):
    feat = CosineRandomFeatures.create(D_IN, 16, gamma=0.3, seed=1)
    return feat.and_then(L2Normalizer()).and_then(
        head or LinearMapEstimator(lam=lam), X, Y
    )


def test_refit_stream_freezes_prefix_and_matches_manual_fold():
    X, Y = _data()
    pipe = _drift_pipeline(X[:100], Y[:100])
    batches = _split(X[100:], Y[100:], [64, 128])
    outs = list(pipe.refit_stream(batches, every=2))
    assert len(outs) == 2  # 2 full ticks + the tail tick of batch 3
    # Frozen featurize: the SAME fitted (fused) prefix object rides
    # every yield; only the head is re-solved per tick.
    t0, t1 = outs[0].transformers(), outs[1].transformers()
    assert t0[0] is t1[0]
    assert t0[-1] is not t1[-1]
    # The head equals a manual fold of the initial problem (the default
    # seed) plus the SAME featurized batches.
    fitted = pipe.fit()
    from keystone_tpu.workflow.online import split_fitted_head

    prefix, _head = split_fitted_head(fitted)
    est = LinearMapEstimator(lam=1e-3)
    st = est.partial_fit(
        np.asarray(prefix.apply(X[:100]).get()), Y[:100]
    )
    for bx, by in batches:
        st = est.partial_fit(np.asarray(prefix.apply(bx).get()), by,
                             state=st)
    manual = est.solve_online(st)
    yielded = outs[-1].transformers()[-1]
    assert np.array_equal(np.asarray(yielded.W), np.asarray(manual.W))
    assert np.array_equal(np.asarray(yielded.b), np.asarray(manual.b))


def test_refit_stream_full_refit_fallback_counted():
    class BatchOnlyHead(LabelEstimator):
        def __init__(self):
            self.fits = 0
            self.fit_rows = []

        def fit(self, X, y):
            self.fits += 1
            X = np.asarray(X, np.float64)
            y = np.asarray(y, np.float64)
            self.fit_rows.append(X.shape[0])
            W = np.linalg.lstsq(X, y, rcond=None)[0]
            return LinearMapper(W.astype(np.float32))

    X, Y = _data()
    head = BatchOnlyHead()
    pipe = _drift_pipeline(X[:100], Y[:100], head=head)
    before = online_counters.get("full_refits")
    before_buf = online_counters.get("batches_buffered")
    before_folded = online_counters.get("batches_folded")
    outs = list(pipe.refit_stream(
        _split(X[100:], Y[100:], [164]), every=1
    ))
    assert len(outs) == 2
    # Initial fit + one FULL refit per tick — the KG105 cost, counted.
    assert head.fits == 3
    assert online_counters.get("full_refits") == before + 2
    # Buffered, not folded: nothing reached retained accumulators.
    assert online_counters.get("batches_buffered") == before_buf + 2
    assert online_counters.get("batches_folded") == before_folded
    # The fallback honors the seed too: each full refit runs over
    # initial ∪ streamed-so-far (100 + 164, then 100 + 200).
    assert head.fit_rows[1:] == [264, 300]
    assert np.asarray(outs[-1].apply(X[:8]).get()).shape == (8, K)


def test_refit_stream_fallback_refuses_forgetting_args():
    """decay/window on a partial_fit-less head must refuse, never
    silently full-refit with every batch weighted equally."""

    class BatchOnlyHead(LabelEstimator):
        def fit(self, X, y):
            return LinearMapper(np.zeros((16, K), np.float32))

    X, Y = _data(n=64)
    pipe = _drift_pipeline(X, Y, head=BatchOnlyHead())
    # EAGER refusal: the call itself refuses (no next() needed) — a
    # never-consumed generator must not swallow the misconfiguration.
    with pytest.raises(ValueError, match="partial_fit head"):
        pipe.refit_stream([(X[:8], Y[:8])], decay=0.5)
    # A caller-supplied state refuses the same way: the fallback would
    # never fold its retained history.
    st = LinearMapEstimator().partial_fit(
        np.zeros((4, 16), np.float32), Y[:4]
    )
    with pytest.raises(ValueError, match="OnlineState"):
        pipe.refit_stream([(X[:8], Y[:8])], state=st)


def test_refit_stream_refuses_non_estimator_sink():
    fitted = CosineRandomFeatures.create(D_IN, 8, seed=0).to_pipeline()
    with pytest.raises(ValueError, match="estimator head"):
        fitted.refit_stream([(np.zeros((2, D_IN)), None)])


# ---------------------------------------------------------------------------
# OnlineTrainer + daemon refresh (the serving half)
# ---------------------------------------------------------------------------


def _serve_daemon_mod():
    sys.path.insert(0, TOOLS)
    try:
        import serve_daemon
    finally:
        sys.path.pop(0)
    return serve_daemon


def _post(port, path, body, headers=None, retries=8):
    return _serve_daemon_mod().http_post(port, path, body, headers,
                                         timeout=60, retries=retries)


def _settle(daemon, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        snap = daemon._flight.snapshot()
        if daemon.stats()["active_requests"] == 0 and all(
            r["outcome"] is not None for r in snap["records"]
        ):
            return snap
        time.sleep(0.01)
    return daemon._flight.snapshot()


def _trainer_rig(tmp_path, decay=0.5):
    """A live daemon on generation 0 + a trainer wired to hot-swap it."""
    from keystone_tpu.workflow.daemon import ServingDaemon

    X, Y = _data(n=256, seed=5)
    pipe = _drift_pipeline(X, Y)
    art0 = str(tmp_path / "model-g0000.kart")
    save_artifact(pipe.fit(), art0, feature_shape=(D_IN,), dtype="float32")
    daemon = ServingDaemon(artifact=art0, http_port=0, enable_socket=False,
                           buckets=(32,), max_batch=32)
    trainer = OnlineTrainer(
        pipe, daemon=daemon, artifact_dir=str(tmp_path), decay=decay,
        refresh_ms=0, start=False, feature_shape=(D_IN,), name="t",
    )
    return daemon, trainer, (X, Y)


def test_trainer_refresh_hot_swaps_live_daemon(tmp_path):
    daemon, trainer, (X, Y) = _trainer_rig(tmp_path)
    try:
        probe = X[:32]
        st, doc = _post(daemon.http_port, "/predict",
                        {"x": probe.tolist()})
        assert st == 200 and doc["generation"] == 0
        Xs, Ys = _data(n=96, seed=9)
        for a, b in ((0, 48), (48, 96)):
            trainer.submit(Xs[a:b], Ys[a:b])
        before = online_counters.get("refreshes_pushed")
        refreshed = trainer.refresh()
        assert online_counters.get("refreshes_pushed") == before + 1
        assert daemon.generation == 1
        assert trainer.last_artifact and os.path.exists(
            trainer.last_artifact)
        # The wire answers with the refreshed model's bits.
        st, doc = _post(daemon.http_port, "/predict",
                        {"x": probe.tolist()})
        assert st == 200 and doc["generation"] == 1
        want = np.asarray(refreshed.apply(probe).get())
        assert np.array_equal(
            np.asarray(doc["y"], dtype=np.float32), want
        )
        snap = _settle(daemon)
        assert all(r["outcome"] is not None for r in snap["records"])
    finally:
        trainer.close()
        daemon.close()


def test_trainer_refresh_emits_traced_telemetry(tmp_path, monkeypatch):
    """The cadence refresh mints ONE trace id and threads it through
    the re-solve -> artifact -> swap chain: with durable export on,
    both lifecycle records (refresh + swap) land on disk carrying that
    id — "why did the model change?" resolves to one grep."""
    import json as _json

    from keystone_tpu.utils.telemetry import (
        TRACE_ID_RE,
        active_telemetry,
        reset_telemetry,
    )

    tel_dir = str(tmp_path / "telemetry")
    monkeypatch.setenv("KEYSTONE_TELEMETRY_DIR", tel_dir)
    reset_telemetry()
    try:
        daemon, trainer, (X, Y) = _trainer_rig(tmp_path)
        try:
            Xs, Ys = _data(n=96, seed=9)
            trainer.submit(Xs[:48], Ys[:48])
            trainer.refresh()
            assert daemon.generation == 1
            tel = active_telemetry()
            assert tel is not None and tel.drain(timeout=20.0)
        finally:
            trainer.close()
            daemon.close()
        records = []
        for name in sorted(os.listdir(tel_dir)):
            with open(os.path.join(tel_dir, name)) as fh:
                records.extend(_json.loads(line) for line in fh)
        refreshes = [r for r in records if r.get("kind") == "refresh"]
        swaps = [r for r in records if r.get("kind") == "swap"]
        assert refreshes and swaps
        tid = refreshes[0]["trace_id"]
        assert TRACE_ID_RE.match(tid)
        assert swaps[0]["trace_id"] == tid
        assert refreshes[0]["folds_applied"] >= 1
        assert swaps[0]["from_generation"] == 0
        assert swaps[0]["generation"] == 1
    finally:
        reset_telemetry()


def test_trainer_refresh_abort_keeps_serving_and_retries(tmp_path, faults):
    """The chaos gate: a refresh killed at the refresh_abort site leaves
    generation 0 answering and the accumulators untouched; the retry
    (the next cadence tick) succeeds from identical state."""
    daemon, trainer, (X, Y) = _trainer_rig(tmp_path)
    try:
        Xs, Ys = _data(n=64, seed=9)
        trainer.submit(Xs, Ys)
        faults("refresh_abort:1")
        # Re-arm the trainer's resolved-once plan (the test flipped the
        # knobs after construction).
        trainer._plan = reliability.active_plan()
        before = online_counters.get("refreshes_failed")
        with pytest.raises(RefreshAborted):
            trainer.refresh()
        assert online_counters.get("refreshes_failed") == before + 1
        # stats() reports COMPLETED publishes, not attempts: a trainer
        # failing every tick must not read as "refreshing".
        assert trainer.stats()["refreshes"] == 0
        assert daemon.generation == 0
        st, doc = _post(daemon.http_port, "/predict",
                        {"x": X[:32].tolist()})
        assert st == 200 and doc["generation"] == 0
        # The retry refreshes from the SAME retained state.
        trainer.refresh()
        assert daemon.generation == 1
    finally:
        trainer.close()
        daemon.close()


def test_trainer_swap_abort_rolls_back_then_recovers(tmp_path, faults):
    """A refresh whose SWAP dies mid-handoff is a rollback, not an
    outage: generation 0 keeps serving, the failure is counted, and the
    next refresh lands."""
    # Armed BEFORE the rig: the daemon resolves its fault plan once at
    # construction (the active_plan discipline); the swap_abort site
    # only fires inside _do_swap, so generation 0 still stands up.
    faults("swap_abort:1")
    daemon, trainer, (X, Y) = _trainer_rig(tmp_path)
    try:
        Xs, Ys = _data(n=64, seed=9)
        trainer.submit(Xs, Ys)
        before = online_counters.get("refreshes_failed")
        with pytest.raises(Exception):
            trainer.refresh()
        assert online_counters.get("refreshes_failed") == before + 1
        assert daemon.generation == 0 and daemon.swap_failures == 1
        # The fold debt survives the failed PUBLISH: the cadence loop
        # still sees work and retries next tick (the counter clears
        # only on a successful publish).
        assert trainer.stats()["folds_since_refresh"] > 0
        st, doc = _post(daemon.http_port, "/predict",
                        {"x": X[:32].tolist()})
        assert st == 200 and doc["generation"] == 0
        trainer.refresh()
        assert daemon.generation == 1
        _settle(daemon)
    finally:
        trainer.close()
        daemon.close()


def test_trainer_checkpoint_resume_bit_identical(tmp_path):
    """A killed trainer process (simulated: a second trainer over the
    same checkpoint_dir) resumes the accumulator checkpoint and
    refreshes to the same bits as an uninterrupted one."""
    X, Y = _data(n=128, seed=5)
    pipe = _drift_pipeline(X, Y)
    Xs, Ys = _data(n=120, seed=9)
    ck_a = str(tmp_path / "ck_a")
    t_a = OnlineTrainer(pipe, refresh_ms=0, start=False,
                        checkpoint_dir=ck_a, name="a")
    t_a.submit(Xs[:40], Ys[:40])
    t_a.submit(Xs[40:70], Ys[40:70])
    t_a.close()  # "killed" — the checkpoint is the survivor
    t_b = OnlineTrainer(pipe, refresh_ms=0, start=False,
                        checkpoint_dir=ck_a, name="b")
    t_b.submit(Xs[70:], Ys[70:])
    resumed = t_b.resolve()
    t_b.close()
    t_c = OnlineTrainer(pipe, refresh_ms=0, start=False, name="c")
    for a, b in ((0, 40), (40, 70), (70, 120)):
        t_c.submit(Xs[a:b], Ys[a:b])
    uninterrupted = t_c.resolve()
    t_c.close()
    W_r = np.asarray(resumed.transformers()[-1].W)
    W_u = np.asarray(uninterrupted.transformers()[-1].W)
    assert np.array_equal(W_r, W_u)


def test_trainer_seeds_initial_problem_and_prunes_artifacts(tmp_path):
    """The first refresh re-solves initial ∪ streamed (never the first
    small batch alone), and artifact retention keeps only the newest
    keep_artifacts files."""
    X, Y = _data(n=128, seed=5)
    pipe = _drift_pipeline(X, Y)
    tr = OnlineTrainer(pipe, artifact_dir=str(tmp_path), refresh_ms=0,
                       start=False, feature_shape=(D_IN,), name="s",
                       keep_artifacts=2)
    try:
        Xs, Ys = _data(n=16, seed=9)
        tr.submit(Xs, Ys)
        got = tr.resolve()
        # Manual: seed with the featurized INITIAL problem, then the
        # streamed batch — bit-identical.
        fitted = pipe.fit()
        from keystone_tpu.workflow.online import split_fitted_head

        prefix, _ = split_fitted_head(fitted)
        est = LinearMapEstimator(lam=1e-3)
        st = est.partial_fit(np.asarray(prefix.apply(X).get()), Y)
        st = est.partial_fit(np.asarray(prefix.apply(Xs).get()), Ys,
                             state=st)
        manual = est.solve_online(st)
        assert np.array_equal(
            np.asarray(got.transformers()[-1].W), np.asarray(manual.W)
        )
        # Retention: 3 refreshes at keep_artifacts=2 leave the newest 2.
        for i in range(3):
            tr.submit(Xs, Ys)
            tr.refresh()
        kept = sorted(p for p in os.listdir(str(tmp_path))
                      if p.startswith("s-g"))
        assert kept == ["s-g0002.kart", "s-g0003.kart"]
        assert tr.stats()["refreshes"] == 3
    finally:
        tr.close()
    # A restarted trainer over the same artifact_dir CONTINUES the
    # sequence past the published files — never a fresh g0001 sorting
    # under a stale g0003.
    tr2 = OnlineTrainer(pipe, artifact_dir=str(tmp_path), refresh_ms=0,
                        start=False, feature_shape=(D_IN,), name="s")
    try:
        Xs, Ys = _data(n=16, seed=9)
        tr2.submit(Xs, Ys)
        tr2.refresh()
        assert os.path.basename(tr2.last_artifact) == "s-g0004.kart"
    finally:
        tr2.close()


def test_trainer_resolve_races_submit_without_deadlock():
    """The off-lock re-solve must never launch mesh collectives
    concurrently with a submit fold (interleaved participant arrivals
    deadlock the XLA rendezvous): the snapshot flushes its pending tail
    UNDER the trainer lock, leaving the off-lock solve collective-free.
    Subprocess-isolated so a regression FAILS (timeout) instead of
    wedging the shared mesh for the rest of the suite."""
    import subprocess

    code = r"""
import os, threading
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from keystone_tpu.workflow.online import OnlineTrainer
from keystone_tpu.nodes.learning.linear_mapper import LinearMapEstimator
from keystone_tpu.nodes.stats.random_features import CosineRandomFeatures

rng = np.random.default_rng(0)
X = rng.normal(size=(200, 10)).astype(np.float32)
Y = rng.normal(size=(200, 3)).astype(np.float32)
feat = CosineRandomFeatures.create(10, 16, gamma=0.3, seed=1)
pipe = feat.and_then(LinearMapEstimator(lam=1e-3), X, Y)
tr = OnlineTrainer(pipe, refresh_ms=0, start=False, name="race")
stop = threading.Event()

def feeder():
    while not stop.is_set():
        tr.submit(X[:24], Y[:24])  # sub-chunk: pending tail always live

t = threading.Thread(target=feeder, daemon=True)
t.start()
for _ in range(6):
    out = tr.resolve()
    assert np.isfinite(np.asarray(out.transformers()[-1].W)).all()
stop.set()
t.join(10)
tr.close()
print("RACE_OK")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0 and "RACE_OK" in proc.stdout, (
        proc.stdout[-2000:], proc.stderr[-2000:],
    )


def test_trainer_resume_mode_conflict_refuses_at_construction(tmp_path):
    """Restarting a trainer with a different forgetting mode (or fold
    granularity) over an existing checkpoint refuses AT CONSTRUCTION —
    not on every submit while the cadence loop silently serves the
    pre-kill model forever."""
    X, Y = _data(n=96, seed=5)
    pipe = _drift_pipeline(X, Y)
    ck = str(tmp_path / "ck")
    t = OnlineTrainer(pipe, refresh_ms=0, start=False,
                      checkpoint_dir=ck, name="m")
    t.submit(X[:32], Y[:32])
    t.close()
    with pytest.raises(OnlineStateError, match="window"):
        OnlineTrainer(pipe, refresh_ms=0, start=False,
                      checkpoint_dir=ck, window=2, name="m")
    with pytest.raises(OnlineStateError, match="chunk_rows"):
        OnlineTrainer(pipe, refresh_ms=0, start=False,
                      checkpoint_dir=ck, chunk_rows=64, name="m")
    # Same mode resumes fine.
    t2 = OnlineTrainer(pipe, refresh_ms=0, start=False,
                       checkpoint_dir=ck, name="m")
    t2.close()
    # γ-weighted history must not continue unweighted: a decayed
    # checkpoint refuses a decay-less restart (a different γ is legal).
    ck2 = str(tmp_path / "ck2")
    td = OnlineTrainer(pipe, refresh_ms=0, start=False,
                       checkpoint_dir=ck2, decay=0.5, name="d")
    td.submit(X[:16], Y[:16])
    td.submit(X[16:32], Y[16:32])  # decay actually applied
    td.close()
    with pytest.raises(OnlineStateError, match="decay"):
        OnlineTrainer(pipe, refresh_ms=0, start=False,
                      checkpoint_dir=ck2, name="d")
    OnlineTrainer(pipe, refresh_ms=0, start=False, checkpoint_dir=ck2,
                  decay=0.7, name="d").close()


def test_trainer_refreshes_serialize(tmp_path):
    """A manual refresh racing the cadence tick must publish in
    snapshot order: whole refreshes hold one mutex end-to-end."""
    X, Y = _data(n=96, seed=5)
    tr = OnlineTrainer(_drift_pipeline(X, Y), artifact_dir=str(tmp_path),
                       refresh_ms=0, start=False, feature_shape=(D_IN,),
                       name="ser")
    try:
        tr.submit(X[:32], Y[:32])
        import threading

        done = threading.Event()
        tr._refresh_lock.acquire()  # stand in for an in-flight refresh
        t = threading.Thread(
            target=lambda: (tr.refresh(), done.set()), daemon=True
        )
        t.start()
        assert not done.wait(0.3)  # blocked behind the held refresh
        tr._refresh_lock.release()
        assert done.wait(30)
        t.join(10)
        assert tr.stats()["refreshes"] == 1
    finally:
        tr.close()


def test_trainer_cadence_loop_refreshes(tmp_path):
    """The background _refresh_loop actually drives a swap (short
    cadence), and close() stops it."""
    daemon, trainer, (X, Y) = _trainer_rig(tmp_path)
    trainer.close()
    trainer2 = OnlineTrainer(
        _drift_pipeline(X, Y), daemon=daemon,
        artifact_dir=str(tmp_path), decay=0.5, refresh_ms=50,
        feature_shape=(D_IN,), name="loop",
    )
    try:
        Xs, Ys = _data(n=64, seed=9)
        trainer2.submit(Xs, Ys)
        deadline = time.monotonic() + 20
        # The trainer counts a refresh after the daemon has swapped: wait
        # for both, or a poll between the two reads 0 refreshes.
        while ((daemon.generation < 1 or trainer2.stats()["refreshes"] < 1)
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert daemon.generation >= 1
        assert trainer2.stats()["refreshes"] >= 1
    finally:
        trainer2.close()
        daemon.close()


def test_trainer_refuses_batch_only_head():
    class BatchOnlyHead(LabelEstimator):
        def fit(self, X, y):
            return LinearMapper(np.zeros((16, K), np.float32))

    X, Y = _data(n=64)
    pipe = _drift_pipeline(X, Y, head=BatchOnlyHead())
    with pytest.raises(OnlineStateError, match="partial_fit"):
        OnlineTrainer(pipe, refresh_ms=0, start=False)


# ---------------------------------------------------------------------------
# A/B serving: two generations, one replica pool (per-tenant routing)
# ---------------------------------------------------------------------------


def test_ab_serving_two_generations_per_tenant(tmp_path):
    from keystone_tpu.workflow.daemon import ServingDaemon, parse_tenants

    X, Y = _data(n=128, seed=5)
    pipe_a = _drift_pipeline(X, Y, lam=1e-3)
    pipe_b = _drift_pipeline(X, Y, lam=1e-1)  # visibly different weights
    a0 = str(tmp_path / "a.kart")
    a1 = str(tmp_path / "b.kart")
    fitted_a, fitted_b = pipe_a.fit(), pipe_b.fit()
    save_artifact(fitted_a, a0, feature_shape=(D_IN,), dtype="float32")
    save_artifact(fitted_b, a1, feature_shape=(D_IN,), dtype="float32")
    tenants = parse_tenants("alpha:ka:0:gold,beta:kb:0:gold")
    daemon = ServingDaemon(artifact=a0, tenants=tenants, http_port=0,
                           enable_socket=False, buckets=(16,), max_batch=16)
    try:
        probe = X[:16]
        want_a = np.asarray(fitted_a.apply(probe).get())
        want_b = np.asarray(fitted_b.apply(probe).get())

        def ask(key):
            st, doc = _post(daemon.http_port, "/predict",
                            {"x": probe.tolist()},
                            headers={"X-Api-Key": key})
            assert st == 200
            return doc["generation"], np.asarray(doc["y"],
                                                 dtype=np.float32)

        # A typo'd tenant name refuses up front — never an experiment
        # that silently serves the candidate zero traffic.
        with pytest.raises(ValueError, match="betta"):
            daemon.ab_swap(a1, tenants=["betta"])
        # Tenant OBJECTS are accepted too (not reduced to their repr).
        cand = daemon.ab_swap(a1, tenants=[tenants["kb"]])
        assert cand == 1
        gen_a, y_a = ask("ka")
        gen_b, y_b = ask("kb")
        assert (gen_a, gen_b) == (0, 1)
        assert np.array_equal(y_a, want_a)
        assert np.array_equal(y_b, want_b)
        stats = daemon.stats()
        assert stats["ab"]["tenants"] == ["beta"]
        # Anonymous /stats redacts the enrolled-tenant names to a count.
        assert daemon.stats(redact_tenants=True)["ab"]["tenants"] == 1
        # A full swap mid-experiment is refused, typed.
        with pytest.raises(RuntimeError, match="A/B"):
            daemon.request_swap(a1)
        # Promote: everyone on the candidate, zero dropped requests.
        assert daemon.promote_ab() == 1
        gen_a, y_a = ask("ka")
        assert gen_a == 1 and np.array_equal(y_a, want_b)
        # A second experiment aborts cleanly back to the live gen.
        daemon.ab_swap(a0, tenants=["alpha"])
        gen_a, y_a = ask("ka")
        assert gen_a == 2 and np.array_equal(y_a, want_a)
        daemon.abort_ab()
        gen_a, y_a = ask("ka")
        assert gen_a == 1 and np.array_equal(y_a, want_b)
        # The aborted candidate's number is BURNED (it served tagged
        # responses): the next experiment never reuses 2.
        assert daemon.ab_swap(a0, tenants=["alpha"]) == 3
        daemon.abort_ab()
        snap = _settle(daemon)
        assert all(r["outcome"] is not None for r in snap["records"])
    finally:
        daemon.close()


# ---------------------------------------------------------------------------
# The bench harness, in-process (make bench-online)
# ---------------------------------------------------------------------------


def test_bench_online_harness_inprocess(tmp_path):
    sys.path.insert(0, TOOLS)
    try:
        import bench_online
    finally:
        sys.path.pop(0)
    rc = bench_online.main(["--quick"])
    assert rc == 0


def test_bench_online_row_shape():
    """The committed fit_online row carries the gate evidence the watch
    family judges (the directions test lives in test_bench_watch)."""
    rows = [json.loads(line)
            for line in open(os.path.join(REPO, "BENCH_fit.json"))]
    online = [r for r in rows if r.get("metric") == "fit_online"]
    assert online, "make bench-online must append its row"
    row = online[-1]
    d = row["detail"]
    assert row["ok"] is True
    assert d["swap_gate"] and d["recovery_gate"] and d["drift_observed"]
    assert d["dropped_requests"] == 0 and d["unresolved"] == 0
    assert d["post_refresh_accuracy"] >= d["full_refit_accuracy"] - 0.05
    assert 1 in d["generations_served"] or d["final_generation"] >= 1
