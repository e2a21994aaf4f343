"""Observability layer: tracing, histograms, and the unified registry.

What is pinned here:

1. ``LatencyHistogram`` percentiles agree with numpy on known samples
   (log-bucket quantization stays inside the documented ~4.4%/bucket),
   and the histogram survives concurrent recording;
2. ``Tracer`` spans nest (parent attribution + time containment), the
   ring buffer is bounded, and recording is thread-safe under the
   serving micro-batcher's worker + concurrent clients;
3. the disabled tracer is INERT: a traced-path fit with
   ``KEYSTONE_TRACE`` unset is bit-identical to the enabled-tracer run
   (the same enabled-but-silent discipline as test_reliability.py);
4. ``Tracer.export`` emits schema-valid Chrome-trace JSON (the shared
   ``validate_chrome_trace`` oracle also rejects malformed documents);
5. ``MetricsRegistry`` unifies counters/histograms/gauges under one
   snapshot/reset, per-bucket compile counts name which bucket compiled,
   and the registry's serving percentiles agree with an external
   stopwatch over the same requests;
6. the ``make trace-demo`` flow (tools/trace_demo.py) runs fast and
   covers every instrumented surface — the tier-1 stand-in for the
   Makefile target.
"""

import importlib.util
import json
import os
import sys
import threading

import numpy as np
import pytest

from keystone_tpu.config import config
from keystone_tpu.utils.metrics import (
    Gauge,
    LatencyHistogram,
    Tracer,
    active_tracer,
    metrics_registry,
    reset_tracer,
    serving_counters,
    validate_chrome_trace,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def traced():
    """Arm process-wide tracing for the test; restores the prior knob and
    drops the cached tracer afterwards (mirror of test_reliability's
    ``faults`` fixture)."""
    prior = config.trace

    def arm(on: bool = True):
        config.trace = on
        reset_tracer()
        return active_tracer()

    try:
        yield arm
    finally:
        config.trace = prior
        reset_tracer()


# ---------------------------------------------------------------------------
# LatencyHistogram
# ---------------------------------------------------------------------------


def _nearest_rank(samples, p):
    s = np.sort(np.asarray(samples))
    return float(s[max(0, int(np.ceil(len(s) * p / 100.0)) - 1)])


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
def test_histogram_percentiles_match_numpy(dist):
    rng = np.random.default_rng(7)
    if dist == "lognormal":
        vals = rng.lognormal(mean=-5.0, sigma=1.2, size=4000)
    elif dist == "uniform":
        vals = rng.uniform(1e-4, 5e-2, size=4000)
    else:
        vals = np.concatenate(
            [rng.normal(2e-3, 1e-4, 2000), rng.normal(8e-2, 5e-3, 2000)]
        ).clip(min=1e-6)
    h = LatencyHistogram()
    for v in vals:
        h.record(float(v))
    for p in (50, 90, 95, 99):
        est = h.percentile(p)
        ref = _nearest_rank(vals, p)
        # One log bucket is 2**(1/16) ~ 4.4% wide; the representative
        # value sits mid-bucket, so <= ~2.2% + rank discreteness.
        assert abs(est - ref) / ref < 0.05, (p, est, ref)
    snap = h.snapshot()
    assert snap["count"] == 4000
    assert snap["min_ms"] == pytest.approx(float(vals.min()) * 1e3, rel=1e-3)
    assert snap["max_ms"] == pytest.approx(float(vals.max()) * 1e3, rel=1e-3)
    # snapshot rounds to 4 decimals of a millisecond (0.1 µs)
    assert snap["mean_ms"] == pytest.approx(float(vals.mean()) * 1e3, rel=1e-3)


def test_histogram_extremes_clamp_not_crash():
    h = LatencyHistogram()
    h.record(0.0)           # below the first bucket
    h.record(-1.0)          # negative clock skew: clamped to 0
    h.record(1e6)           # beyond the top bucket
    assert h.count == 3
    assert h.percentile(50) is not None
    assert h.snapshot()["max_ms"] == pytest.approx(1e9)


def test_histogram_nonpositive_samples_clamped_and_counted():
    """The satellite guard: non-positive samples never reach the log
    math — they clamp to the minimum bucket and show up as a
    ``dropped_nonpositive`` count in the snapshot, so a clock that
    misbehaves is visible instead of silently skewing the low tail."""
    h = LatencyHistogram()
    h.record(-3.0)
    h.record(0.0)
    h.record(0.01)
    snap = h.snapshot()
    assert snap["count"] == 3
    assert snap["dropped_nonpositive"] == 2
    assert snap["min_ms"] == pytest.approx(1e-3)  # clamped to min bucket
    assert h.percentile(50) is not None
    dist = h.buckets()
    assert dist["dropped_nonpositive"] == 2
    assert dist["buckets"][0][0] == pytest.approx(1e-6)
    assert dist["buckets"][-1][1] == 3  # cumulative reaches the count
    h.reset()
    assert "dropped_nonpositive" not in h.snapshot()  # zero = absent


def test_histogram_empty_and_reset():
    h = LatencyHistogram()
    assert h.percentile(99) is None
    assert h.snapshot() == {"count": 0}
    h.record(0.01)
    assert h.count == 1
    h.reset()
    assert h.snapshot() == {"count": 0}


def test_histogram_concurrent_recording():
    h = LatencyHistogram()
    n_threads, per = 8, 2000

    def work(seed):
        r = np.random.default_rng(seed)
        for v in r.uniform(1e-4, 1e-1, per):
            h.record(float(v))

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == n_threads * per
    assert 1e-4 <= h.percentile(50) <= 1e-1


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_span_nesting_parent_and_containment():
    tr = Tracer(128)
    with tr.span("outer", "t"):
        with tr.span("inner", "t", rows=3):
            pass
    spans = {s["name"]: s for s in tr.spans()}
    inner, outer = spans["inner"], spans["outer"]
    assert inner["args"]["parent"] == "outer"
    assert inner["args"]["rows"] == 3
    assert "parent" not in outer["args"]
    assert inner["tid"] == outer["tid"]
    assert outer["start_ns"] <= inner["start_ns"]
    assert (inner["start_ns"] + inner["dur_ns"]
            <= outer["start_ns"] + outer["dur_ns"])


def test_span_yields_attrs_for_late_annotation():
    tr = Tracer(16)
    with tr.span("node", "t") as attrs:
        attrs["shape"] = [4, 2]
    assert tr.spans()[0]["args"]["shape"] == [4, 2]


def test_ring_buffer_bounded():
    tr = Tracer(32)
    for i in range(100):
        tr.instant(f"e{i}", "t")
    spans = tr.spans()
    assert len(spans) == 32
    assert tr.dropped == 100 - 32
    assert spans[0]["name"] == "e68"  # most recent 32 kept


def test_active_tracer_gate_and_rebuild(traced):
    assert active_tracer() is None  # disabled by default in tests
    tr = traced(True)
    assert tr is not None and active_tracer() is tr  # cached instance
    traced(False)
    assert active_tracer() is None


def test_tracer_thread_safety_under_micro_batcher(traced):
    from keystone_tpu.nodes.stats.normalizer import L2Normalizer
    from keystone_tpu.workflow.serving import CompiledPipeline, PipelineService

    tr = traced(True)
    cp = CompiledPipeline(L2Normalizer(), max_batch=8)
    cp.warmup((4,))
    n_clients, per = 4, 10
    errs = []

    def client(cid):
        rng = np.random.default_rng(cid)
        try:
            for _ in range(per):
                x = rng.normal(size=(4,)).astype(np.float32)
                with tr.span("client.request", "test", client=cid):
                    svc.submit(x).result(timeout=30)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    with PipelineService(cp, max_delay_ms=1.0) as svc:
        threads = [
            threading.Thread(target=client, args=(c,))
            for c in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errs
    spans = tr.spans()
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # Every request got a lifecycle span from the worker thread and a
    # client span from its own thread — recorded concurrently.
    ok = [s for s in by_name["serve.request"]
          if s["args"].get("outcome") == "ok"]
    assert len(ok) == n_clients * per
    assert len(by_name["client.request"]) == n_clients * per
    assert len(by_name["serve.queued"]) == n_clients * per
    assert len({s["tid"] for s in spans}) >= n_clients + 1
    # And the whole concurrent recording exports as a valid trace.
    assert validate_chrome_trace(tr.export()) == []


def test_disabled_tracer_fit_bit_identity(traced):
    """Enabled-but-recording vs disabled tracing produce bit-identical
    solver output — spans observe, never perturb (the reliability
    harness's enabled-but-silent discipline)."""
    from keystone_tpu.linalg import solve_least_squares_chunked
    from keystone_tpu.loaders.stream import BatchIterator

    rng = np.random.default_rng(3)
    X = rng.normal(size=(96, 12)).astype(np.float32)
    Y = (X @ rng.normal(size=(12, 4))).astype(np.float32)

    def solve():
        it = BatchIterator.from_arrays(X, Y, batch_rows=16).prefetch(2)
        return np.asarray(solve_least_squares_chunked(it, lam=1e-3))

    traced(False)
    base = solve()
    tr = traced(True)
    armed = solve()
    assert len(tr.spans()) > 0  # it really did trace
    traced(False)
    again = solve()
    np.testing.assert_array_equal(base, armed)
    np.testing.assert_array_equal(base, again)


def test_traced_pipeline_fit_bit_identity(traced):
    from keystone_tpu.nodes.stats.scalers import StandardScaler
    from keystone_tpu.workflow.executor import PipelineEnv

    rng = np.random.default_rng(4)
    X = rng.normal(size=(32, 6)).astype(np.float32)

    def fit_apply():
        PipelineEnv.reset()  # a real refit, not a fit-cache hit
        return np.asarray(
            StandardScaler().with_data(X).fit().apply(X).get()
        )

    traced(False)
    base = fit_apply()
    tr = traced(True)
    armed = fit_apply()
    names = {s["name"] for s in tr.spans()}
    assert "pipeline.fit" in names and "pipeline.apply" in names
    assert any(n.startswith("node:") for n in names)
    np.testing.assert_array_equal(base, armed)


# ---------------------------------------------------------------------------
# Chrome-trace export / schema
# ---------------------------------------------------------------------------


def test_export_schema_valid_and_written(tmp_path):
    tr = Tracer(64)
    with tr.span("a", "cat", rows=5):
        tr.instant("marker", "cat")
    path = str(tmp_path / "trace.json")
    doc = tr.export(path)
    assert validate_chrome_trace(doc) == []
    with open(path) as f:
        reloaded = json.load(f)
    assert validate_chrome_trace(reloaded) == []
    xs = [e for e in reloaded["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"a", "marker"}
    metas = [e for e in reloaded["traceEvents"] if e["ph"] == "M"]
    assert metas and metas[0]["args"]["name"]  # thread_name metadata


def test_validate_rejects_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": "nope"}) != []
    bad_phase = {"traceEvents": [{"name": "x", "ph": "Q", "pid": 1}]}
    assert validate_chrome_trace(bad_phase) != []
    neg = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": -5}
    ]}
    assert any("negative" in e for e in validate_chrome_trace(neg))
    no_ts = {"traceEvents": [{"name": "x", "ph": "X", "pid": 1}]}
    assert validate_chrome_trace(no_ts) != []


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


def test_registry_unifies_counters_histograms_gauges():
    snap = metrics_registry.snapshot()
    # The process counter sets live under the one registry...
    assert "serving" in snap and "reliability" in snap
    assert snap["serving"] == serving_counters.snapshot()
    # ...histograms and gauges are get-or-create singletons...
    h = metrics_registry.histogram("test.latency")
    assert metrics_registry.histogram("test.latency") is h
    g = metrics_registry.gauge("test.depth")
    assert metrics_registry.gauge("test.depth") is g
    assert isinstance(g, Gauge)
    # ...with type-confusion refused, not silently served.
    with pytest.raises(TypeError):
        metrics_registry.gauge("test.latency")
    h.record(0.005)
    g.set(3)
    g.set(1)
    snap = metrics_registry.snapshot()
    assert snap["test.latency"]["count"] >= 1
    assert snap["test.depth"] == {"value": 1, "max": 3}
    h.reset()
    g.reset()


def test_registry_reset_resets_every_component():
    h = metrics_registry.histogram("test.reset_probe")
    h.record(0.1)
    serving_counters.record_call(8, 5)
    metrics_registry.reset()
    snap = metrics_registry.snapshot()
    assert snap["test.reset_probe"] == {"count": 0}
    assert snap["serving"]["calls"] == 0


def test_registry_snapshot_under_concurrent_writers():
    """The satellite gate: 4 writer threads hammer counters, histograms,
    and a gauge while a reader snapshots in a loop — no exceptions, no
    torn reads, and every successive counter view is monotone."""
    counters = metrics_registry.counters("test.concurrent_counts")
    hist = metrics_registry.histogram("test.concurrent_lat")
    gauge = metrics_registry.gauge("test.concurrent_depth")
    counters.reset()
    hist.reset()
    gauge.reset()
    n_threads, per = 4, 3000
    stop = threading.Event()
    errs: list = []

    def writer(tid):
        try:
            for i in range(per):
                counters.bump("total")
                counters.bump(f"w{tid}")
                hist.record(1e-4 * (1 + (i % 7)))
                gauge.set(i)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    views: list = []

    def reader():
        try:
            while not stop.is_set():
                snap = metrics_registry.snapshot()
                views.append(snap["test.concurrent_counts"].get("total", 0))
                assert snap["test.concurrent_lat"]["count"] >= 0
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    rt = threading.Thread(target=reader)
    rt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rt.join()
    assert not errs, errs[:2]
    # Monotone counter views: no snapshot ever ran backwards.
    assert all(a <= b for a, b in zip(views, views[1:]))
    snap = metrics_registry.snapshot()
    assert snap["test.concurrent_counts"]["total"] == n_threads * per
    assert all(
        snap["test.concurrent_counts"][f"w{t}"] == per
        for t in range(n_threads)
    )
    assert snap["test.concurrent_lat"]["count"] == n_threads * per
    counters.reset()
    hist.reset()
    gauge.reset()


def test_prometheus_exposition_valid_and_agrees_with_snapshot():
    """The export-surface gate, registry-side: ``prometheus()`` parses
    under the shared validator, carries instance labels, and its sample
    values agree with ``snapshot()``."""
    from keystone_tpu.utils.metrics import (
        parse_prometheus_text,
        validate_prometheus_text,
    )

    h = metrics_registry.histogram("test.prom_lat")
    h.reset()
    for v in (0.001, 0.002, 0.004, 0.5):
        h.record(v)
    g = metrics_registry.gauge("test.prom_depth[inst0]")
    g.set(7)
    c = metrics_registry.counters("test.prom_counts[inst0]")
    c.reset()
    c.bump("ok", 3)
    c.bump("error")
    text = metrics_registry.prometheus()
    assert validate_prometheus_text(text) == []
    samples = {
        (s["name"], tuple(sorted(s["labels"].items()))): s["value"]
        for s in parse_prometheus_text(text)
    }
    assert samples[("keystone_test_prom_lat_seconds_count", ())] == 4
    assert samples[
        ("keystone_test_prom_lat_seconds_sum", ())
    ] == pytest.approx(0.507)
    assert samples[
        ("keystone_test_prom_depth", (("instance", "inst0"),))
    ] == 7
    assert samples[
        ("keystone_test_prom_counts_total",
         (("instance", "inst0"), ("key", "ok")))
    ] == 3
    assert samples[
        ("keystone_test_prom_counts_total",
         (("instance", "inst0"), ("key", "error")))
    ] == 1
    # Quantiles ride along as gauges in seconds.
    q99 = samples[
        ("keystone_test_prom_lat_quantile_seconds", (("quantile", "0.99"),))
    ]
    assert q99 == pytest.approx(h.snapshot()["p99_ms"] / 1e3)
    # The serving counter component flattens with its bucket maps.
    serving_counters.record_call(8, 5)
    text = metrics_registry.prometheus()
    assert validate_prometheus_text(text) == []
    bucket_hits = [
        s for s in parse_prometheus_text(text)
        if s["name"] == "keystone_serving_bucket_hits"
    ]
    assert any(
        s["labels"].get("key") == "8" and s["value"] >= 1
        for s in bucket_hits
    )
    serving_counters.reset()
    h.reset()
    g.reset()
    c.reset()


def test_prometheus_label_escaping_round_trips():
    """Escape decoding is single-pass: a label value with a literal
    backslash before an 'n' must round-trip, not decode the tail of the
    escaped backslash as a newline escape."""
    from keystone_tpu.utils.metrics import (
        _prom_labels,
        parse_prometheus_text,
    )

    for value in ("dir\\name", 'quo"te', "line\nbreak", "\\\\n", "plain"):
        line = f"m{_prom_labels({'k': value})} 1\n"
        (sample,) = parse_prometheus_text(line)
        assert sample["labels"]["k"] == value, (value, sample)


def test_retain_request_since_bound_keeps_journey_drops_scan():
    """The bounded tail-sampling scan: spans recorded before the request
    existed are skipped via early exit, spans of its journey are kept."""
    tr = Tracer(256)
    for i in range(50):  # old unrelated traffic, ends well before t_sub
        tr.instant(f"old{i}", "t", req_id=999)
    import time as _t

    _t.sleep(0.02)  # clear the scan slack so the cutoff really binds
    t_sub = Tracer.now()
    tr.record("serve.queued", "serving", t_sub, req_id=7)
    tr.record("serve.device", "serving", t_sub, req_ids=[7, 8])
    tr.record("serve.request", "serving", t_sub, req_id=7, outcome="ok")
    n = tr.retain_request(7, since_ns=t_sub)
    assert n == 3
    kept = tr.retained()[7]
    assert [s["name"] for s in kept] == [
        "serve.queued", "serve.device", "serve.request",
    ]
    # Without since_ns the full ring scan finds the same spans.
    tr2 = Tracer(256)
    tr2.record("serve.request", "serving", Tracer.now(), req_id=3)
    assert tr2.retain_request(3) == 1


def test_validate_prometheus_rejects_malformed():
    from keystone_tpu.utils.metrics import validate_prometheus_text

    assert validate_prometheus_text("not a metric line\n") != []
    assert validate_prometheus_text('x{key=unquoted} 1\n') != []
    assert validate_prometheus_text("x 1e999e9\n") != []
    assert validate_prometheus_text("# TYPE x wrongtype\nx 1\n") != []
    # Histogram discipline: buckets must be cumulative and +Inf-capped.
    bad = (
        "# TYPE h histogram\n"
        'h_bucket{le="0.1"} 5\nh_bucket{le="+Inf"} 3\nh_count 3\n'
    )
    assert any("cumulative" in e for e in validate_prometheus_text(bad))
    no_inf = "# TYPE h histogram\n" 'h_bucket{le="0.1"} 5\n'
    assert any("+Inf" in e for e in validate_prometheus_text(no_inf))
    # A validator reports, never raises — even on a non-numeric le.
    bad_le = "# TYPE h histogram\n" 'h_bucket{le="abc"} 3\n'
    assert any("non-numeric le" in e for e in validate_prometheus_text(bad_le))


def test_record_compile_attributes_bucket():
    """The satellite fix: record_compile(bucket) must no longer drop its
    argument — warmup evidence names which bucket compiled."""
    from keystone_tpu.nodes.stats.normalizer import L2Normalizer
    from keystone_tpu.workflow.serving import CompiledPipeline

    serving_counters.reset()
    # devices=1 pins the single-replica attribution this test is about;
    # the replica pool multiplies every bucket count by the pool width.
    cp = CompiledPipeline(L2Normalizer(), buckets=(2, 4, 16), devices=1)
    cp.warmup((3,))
    snap = serving_counters.snapshot()
    assert snap["compiles_by_bucket"] == {2: 1, 4: 1, 16: 1}
    assert snap["compiles"] == 3
    assert cp.stats()["compiles_by_bucket"] == {2: 1, 4: 1, 16: 1}
    serving_counters.reset()


def test_registry_latency_agrees_with_external_stopwatch():
    """The acceptance cross-check, in miniature: the registry's serving
    percentiles vs an external timer around the same calls, within 10%."""
    import time

    from keystone_tpu.nodes.stats.normalizer import L2Normalizer
    from keystone_tpu.nodes.stats.random_features import CosineRandomFeatures
    from keystone_tpu.workflow.pipeline import FusedTransformer
    from keystone_tpu.workflow.serving import CompiledPipeline

    # A chain heavy enough that per-call latency is well clear of the
    # few-µs Python overhead outside the recorded interval — the regime
    # the 10% contract is about (bench_serve's real serving heads are
    # ms-scale; a bare normalizer at ~50 µs is not).
    chain = FusedTransformer(
        [CosineRandomFeatures.create(32, 512, seed=0), L2Normalizer()]
    )
    cp = CompiledPipeline(chain, max_batch=64)
    cp.warmup((32,))
    hist = metrics_registry.histogram("serve.request_latency")
    hist.reset()
    rng = np.random.default_rng(0)
    lats = []
    for _ in range(80):
        x = rng.normal(size=(int(rng.integers(1, 65)), 32)).astype(np.float32)
        t0 = time.perf_counter()
        cp(x)
        lats.append(time.perf_counter() - t0)
    snap = hist.snapshot()
    assert snap["count"] == 80
    for p in (50, 95, 99):
        ext_ms = _nearest_rank(lats, p) * 1e3
        reg_ms = snap[f"p{p}_ms"]
        assert abs(reg_ms - ext_ms) / ext_ms < 0.10, (p, reg_ms, ext_ms)
    hist.reset()


def test_service_stats_health_surface(traced):
    from keystone_tpu.nodes.stats.normalizer import L2Normalizer
    from keystone_tpu.workflow.serving import (
        CompiledPipeline,
        PipelineService,
        e2e_latency,
    )

    e2e_latency.reset()
    cp = CompiledPipeline(L2Normalizer(), max_batch=8)
    cp.warmup((4,))
    svc = PipelineService(cp, max_delay_ms=1.0)
    futs = [
        svc.submit(np.ones((4,), dtype=np.float32)) for _ in range(5)
    ]
    for f in futs:
        f.result(timeout=30)
    stats = svc.stats()
    assert stats["requests"] == 5
    assert stats["worker_alive"] and not stats["closed"]
    assert stats["latency"]["count"] == 5
    assert stats["latency"]["p99_ms"] >= stats["latency"]["p50_ms"]
    assert stats["compiled"]["ladder"] == list(cp.ladder)
    svc.close()
    assert svc.stats()["closed"]


# ---------------------------------------------------------------------------
# trace-demo (the `make trace-demo` flow, in-process for tier-1)
# ---------------------------------------------------------------------------


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_demo_full_coverage(tmp_path):
    """One small fit+serve under tracing must produce a schema-valid
    export whose spans cover executor nodes, solver chunks, prefetch
    residency, and the serving request lifecycle — the acceptance
    surface, and the in-process stand-in for `make trace-demo`."""
    demo = _load_tool("trace_demo")
    out = str(tmp_path / "demo_trace.json")
    result = demo.run_demo(out)
    assert result["schema_errors"] == []
    assert result["missing_coverage"] == []
    assert result["ok"] is True
    assert result["serving_latency"]["count"] == result["service_requests"]
    # the exported artifact round-trips through the report CLI's summary
    report = _load_tool("trace_report")
    with open(out) as f:
        doc = json.load(f)
    rows = report.summarize(doc)
    assert any(k.startswith("solver/") for k in rows)
    assert any(k.startswith("serving/") for k in rows)
    # and tracing was left OFF for the rest of the suite
    assert active_tracer() is None


# ---------------------------------------------------------------------------
# Fit-plane spans: one switch, one clock, identity
# ---------------------------------------------------------------------------


@pytest.fixture
def session(tmp_path):
    """A live ``jax.profiler`` session as the benchmark starts one (no
    Python tracer); stops it and drops the cached tracer afterwards."""
    import jax

    from keystone_tpu.utils.metrics import reset_tracer

    reset_tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    live = []

    def start():
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        live.append(True)
        return str(tmp_path)

    def stop():
        if live:
            live.pop()
            jax.profiler.stop_trace()

    try:
        yield start, stop
    finally:
        stop()
        reset_tracer()


def _host_annotations(trace_dir, prefix="ks:"):
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        found.setdefault(ev.name, []).append(dict(ev.stats))
    return found


def test_profiler_session_arms_the_tracer_and_the_ring_outlives_it(session):
    from keystone_tpu.utils.metrics import recorded_tracer

    start, stop = session
    assert not config.trace and active_tracer() is None
    trace_dir = start()
    tr = active_tracer()
    assert tr is not None and active_tracer() is tr
    with tr.span("fisher.fetch", "featurizer", bytes=10, shape=[2, 5]):
        pass
    stop()
    assert active_tracer() is None  # the session was the only switch
    assert recorded_tracer() is tr  # ... and the ring is still readable
    (span,) = tr.spans()
    assert span["name"] == "fisher.fetch" and span["args"]["bytes"] == 10
    # One clock: the span's mirror lies in the session's own trace, under
    # the fixed prefix, with its scalar attrs.
    mirrors = _host_annotations(trace_dir)
    assert mirrors["ks:fisher.fetch"][0]["bytes"] == 10
    assert "shape" not in mirrors["ks:fisher.fetch"][0]


def test_span_identity_tells_equal_names_apart():
    tr = Tracer(64)
    with tr.span("fit", "t"):
        with tr.span("node:apply", "t"):
            with tr.span("leaf", "t"):
                pass
        with tr.span("node:apply", "t"):
            pass
    with tr.span("fit", "t"):
        with tr.span("node:apply", "t"):
            pass
    spans = tr.spans()
    assert len({s["id"] for s in spans}) == len(spans) == 6
    first, second = [s for s in spans if s["name"] == "fit"]
    assert first["parent_id"] is None and first["root_id"] == first["id"]
    assert second["root_id"] == second["id"] != first["id"]
    nodes = [s for s in spans if s["name"] == "node:apply"]
    assert [n["parent_id"] for n in nodes] == [first["id"], first["id"], second["id"]]
    assert [n["root_id"] for n in nodes] == [first["id"], first["id"], second["id"]]
    (leaf,) = [s for s in spans if s["name"] == "leaf"]
    assert leaf["parent_id"] == nodes[0]["id"] and leaf["root_id"] == first["id"]
    assert leaf["args"]["parent"] == "node:apply"  # the name stays too


def test_record_takes_the_span_open_around_it_as_parent():
    tr = Tracer(16)
    before = tr.now()
    with tr.span("outer", "t"):
        with tr.span("inner", "t"):
            t0 = tr.now()
            tr.record("inside", "t", t0)
            # Began before either span opened: enclosed by neither.
            tr.record("straddles", "t", before)
    tr.instant("alone", "t")
    by_name = {s["name"]: s for s in tr.spans()}
    assert by_name["inside"]["parent_id"] == by_name["inner"]["id"]
    assert by_name["inside"]["root_id"] == by_name["outer"]["id"]
    assert by_name["straddles"]["parent_id"] is None
    assert by_name["alone"]["parent_id"] is None
    assert by_name["alone"]["root_id"] == by_name["alone"]["id"]


def test_compile_listener_puts_a_fresh_jit_under_the_open_span(traced):
    import jax
    import jax.numpy as jnp

    tr = traced(True)

    def fresh_program_for_the_listener(x):
        return jnp.tanh(x) * 3.0 + 1.0

    with tr.span("node:fresh", "executor"):
        jax.jit(fresh_program_for_the_listener)(jnp.ones((7,))).block_until_ready()
    spans = tr.spans()
    (node,) = [s for s in spans if s["name"] == "node:fresh"]
    mine = [s for s in spans
            if "fresh_program_for_the_listener" in s["args"].get("fun_name", "")]
    assert {s["name"] for s in mine} == {"jax.trace", "jax.lower", "jax.compile"}
    lo, hi = node["start_ns"], node["start_ns"] + node["dur_ns"]
    slack = 5_000_000  # time.time() endpoints laid on the ring's clock
    for s in mine:
        assert s["parent_id"] == node["id"] and s["root_id"] == node["id"]
        assert s["cat"] == "jax" and s["dur_ns"] > 0
        assert lo - slack <= s["start_ns"] and s["start_ns"] + s["dur_ns"] <= hi + slack
    # A second call builds nothing: no new record.
    count = len(tr.spans())
    with tr.span("node:fresh", "executor"):
        pass
    assert len(tr.spans()) == count + 1


def test_imagenet_fit_under_a_session_is_one_tree_with_every_span(session):
    from keystone_tpu.loaders.imagenet import ImageNetLoader
    from keystone_tpu.pipelines.images import imagenet_sift_lcs_fv as imagenet

    conf = imagenet.resolve_scale(imagenet.ImageNetSiftLcsFVConfig(
        synthetic_n=48, synthetic_classes=4, pca_dims=8, gmm_k=4,
        gmm_iters=2, descriptor_sample=1000, num_iters=2, block_size=32,
    ))
    train, _test = ImageNetLoader.synthetic(48, 4, size=32, seed=conf.seed)
    start, stop = session
    trace_dir = start()
    imagenet.fit(conf, train, 4)
    stop()
    from keystone_tpu.utils.metrics import recorded_tracer

    spans = recorded_tracer().spans()
    roots = [s for s in spans if s["parent_id"] is None]
    assert [r["name"] for r in roots] == ["fit"]
    (root,) = roots
    assert root["args"]["rows"] == 48
    # The mesh the fit's reductions cross (the tests' 8 devices), the rows'
    # share of each, and what those reductions were handed, from shapes.
    assert root["args"]["shards"] == 8 and root["args"]["rows_per_shard"] == 6
    assert root["args"]["collective_bytes"] > 0
    from keystone_tpu.utils.metrics import DEVICE_SCOPES

    assert {"coll.gram", "coll.atr", "coll.moments", "coll.em", "coll.sample"} <= set(
        DEVICE_SCOPES)
    assert all(s["root_id"] == root["id"] for s in spans)
    names = {s["name"] for s in spans}
    table = {"fit", "pipeline.fit", "fisher.describe", "fisher.sample",
             "fisher.project", "pca.fit", "gmm.fit", "fisher.mixture",
             "solver.setup", "solver.stack", "solver.factor", "solver.epochs",
             "jax.trace", "jax.lower", "jax.compile"}
    assert table <= names, table - names
    assert any(n.startswith("node:") for n in names)
    by_id = {s["id"]: s for s in spans}
    for s in spans:  # every solver span lies under the solver's node
        if s["name"].startswith("solver."):
            assert by_id[s["parent_id"]]["name"].startswith("node:Block")
    # What the root kept for itself until PR 36: the encoder's fetch of the
    # fitted mixture, once a branch, right under the root.
    mixtures = [s for s in spans if s["name"] == "fisher.mixture"]
    assert [m["parent_id"] for m in mixtures] == [root["id"]] * 2
    assert all(m["args"] == {"k": 4, "on_device": 1, "parent": "fit"} for m in mixtures)
    (setup,) = [s for s in spans if s["name"] == "solver.setup"]
    assert {k: setup["args"][k] for k in ("rows", "dim", "classes")} == {
        "rows": 48, "dim": 2 * 2 * 4 * 8, "classes": 4}
    stack = next(s for s in spans if s["name"] == "solver.stack")
    assert setup["start_ns"] + setup["dur_ns"] <= stack["start_ns"]
    # The benchmark's reader sees the new children: the root's own time and
    # the solver's node's are what no span below them names.
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import spanreaders

        ctx = {"fits": 1}
        window = spanreaders.window(ctx, ring=spans)
        setup_ms = spanreaders.span_self_ms(ctx, "solver.setup")
        coverage = spanreaders.span_coverage(ctx)
    finally:
        sys.path.pop(0)
    assert window["self_ns"]["solver.setup"] > 0
    assert setup_ms == pytest.approx(window["self_ns"]["solver.setup"] / 1e6)
    assert window["self_ns"]["fisher.mixture"] == sum(m["dur_ns"] for m in mixtures)
    children = sum(s["dur_ns"] for s in spans if s["parent_id"] == root["id"])
    assert coverage == pytest.approx(100.0 * children / root["dur_ns"], abs=0.5)
    # The descriptors never come to the host: nothing is fetched, nothing
    # flattened there, and each branch's sample is gathered on the device
    # from all of its descriptors.
    assert not {"fisher.fetch", "fisher.flatten"} & names
    described = [s for s in spans if s["name"] == "pipeline.apply"
                 and by_id[s["parent_id"]]["name"] == "fisher.describe"]
    expected = []
    for d in described:
        n, m, width = d["args"]["shape"]
        expected.append({"rows_in": n * m, "rows_out": 1000, "on_device": 1,
                         "bytes": 1000 * width * 4})
    sampled = [s["args"] for s in spans if s["name"] == "fisher.sample"]
    assert len(described) == len(sampled) == 2
    assert [{k: a[k] for k in e} for a, e in zip(sampled, expected)] == expected
    # Each branch's spans say which branch and how much they moved: the
    # descriptors' width on ``fisher.describe``, the sample's rows on
    # ``fisher.project``, and no byte uploaded for the PCA or the mixture.
    assert [by_id[d["parent_id"]]["args"]["branch"] for d in described] == [
        d["args"]["shape"][-1] for d in described]
    assert [s["args"]["rows"] for s in spans if s["name"] == "fisher.project"] == [1000] * 2
    assert [s["args"]["bytes"] for s in spans if s["name"] in ("pca.fit", "gmm.fit")] == [0] * 4
    # The same spans are in the session's trace, on the profiler's clock.
    mirrors = _host_annotations(trace_dir)
    assert {"ks:" + n for n in table if not n.startswith("jax.")} <= set(mirrors)
    assert not {"ks:fisher.fetch", "ks:fisher.flatten"} & set(mirrors)
    assert [{k: int(m[k]) for k in e}
            for m, e in zip(mirrors["ks:fisher.sample"], expected)] == expected


def test_the_inverse_scope_says_whether_the_blocked_inverse_engaged(traced, rng):
    """``solver.factor`` carries what a reader reads (``blocks``, ``chunk``);
    whether the blocked inverse ran is in the program, under the scope
    ``solver.inverse``: one ``triangular_solve`` where the block is one leaf
    (every toy width), one a leaf and products between them where it is cut
    (the two cells' blocks, 4096 and 8192, two and three times)."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.linalg import RowMatrix, bcd

    tr = traced(True)
    A = rng.normal(size=(64, 16)).astype(np.float32)
    B = rng.normal(size=(64, 3)).astype(np.float32)
    bcd.block_coordinate_descent(
        RowMatrix.from_array(A), RowMatrix.from_array(B), block_size=8,
        num_iters=2, lam=0.1, cache_grams=True)
    (factor,) = [s for s in tr.spans() if s["name"] == "solver.factor"]
    assert factor["args"] == {"blocks": 2, "chunk": bcd._factor_chunk(8)}
    assert [bcd._inv_levels(b) for b in (1024, 4096, 8192)] == [0, 2, 3]

    def under_inverse(leaf):
        jaxpr = jax.make_jaxpr(lambda g: bcd._batched_spd_inv(g, leaf))(
            jnp.eye(8, dtype=jnp.float32)[None])
        names = [(str(e.source_info.name_stack), e.primitive.name) for e in jaxpr.eqns]
        return [prim for stack, prim in names if "solver.inverse" in stack]

    # One leaf: Y = solve(L, I) and one product YᵀY. Cut once: two leaves'
    # solves, and the products that join the halves.
    whole, cut = under_inverse(8), under_inverse(4)
    assert whole.count("dot_general") == 1 and cut.count("dot_general") == 6


def test_solver_programs_keep_the_names_the_benchmark_filters_on():
    """``benchmark/metrics/{solver_roofline,factor_ms,featurize_ms}.json``
    tell the solver's device time by the HLO module name ``jit_local``: a
    rename needs their readers changed first."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.linalg import RowMatrix, bcd

    mesh, axis = RowMatrix.from_array(np.zeros((16, 4), np.float32)).mesh, config.data_axis
    rows, nb, b, k = 16 * mesh.shape[axis], 2, 8, 3
    f32 = jnp.float32
    shape = jax.ShapeDtypeStruct
    precision = bcd._precision()
    fold = bcd.fold_blocks(mesh.shape[axis])
    a3, lam, w_rows = shape((nb, rows, b), f32), shape((), f32), shape((rows,), f32)
    lowered = {
        "stack": bcd._stack_blocks_fn(mesh, axis, nb).lower(shape((rows, nb * b), f32)),
        "factor": bcd._fused_factor_fn(mesh, axis, precision, True, fold).lower(
            a3, lam, w_rows),
        "epochs": bcd._fused_epochs_fn(mesh, axis, precision, True, 2, True, fold).lower(
            a3, shape((nb, b, b), f32), shape((rows, k), f32), shape((nb, b, k), f32),
            lam, w_rows),
    }
    for phase, low in lowered.items():
        assert "module @jit_local " in low.as_text(), phase


def _compiled_scopes(lowered):
    """{scope: operations} of a lowered program's compiled ``op_name``s, by
    the rule the trace reader applies to a device trace's ``tf_op``."""
    import collections
    import re

    from keystone_tpu.utils.device_trace import scope_of

    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    return collections.Counter(scope_of(n) for n in names), names


def _solver_lowerings():
    import jax
    import jax.numpy as jnp

    from keystone_tpu.linalg import RowMatrix, bcd
    from keystone_tpu.nodes.learning import GaussianKernelGenerator, kernel_ridge

    mesh, axis = RowMatrix.from_array(np.zeros((16, 4), np.float32)).mesh, config.data_axis
    rows, nb, b, k = 16 * mesh.shape[axis], 2, 8, 3
    f32, shape = jnp.float32, jax.ShapeDtypeStruct
    precision = bcd._precision()
    fold = bcd.fold_blocks(mesh.shape[axis])
    a3, lam, w_rows = shape((nb, rows, b), f32), shape((), f32), shape((rows,), f32)

    def epochs(cached, pad=0):
        return bcd._fused_epochs_fn(mesh, axis, precision, True, 2, cached, fold, pad).lower(
            a3, shape((nb, b, b) if cached else (nb, 1, 1), f32), shape((rows, k), f32),
            shape((nb, b, k), f32), lam, w_rows)

    return {
        "stack": lambda: bcd._stack_blocks_fn(mesh, axis, nb, 2).lower(
            shape((rows, nb * b - 2), f32)),
        "factor": lambda: bcd._fused_factor_fn(mesh, axis, precision, True, fold, 2).lower(
            a3, lam, w_rows),
        "cached epochs": lambda: epochs(True),
        "uncached epochs": lambda: epochs(False, 2),
        "streamed first epoch": lambda: bcd._first_epoch_update_fn(
            mesh, axis, precision, True, fold).lower(
            shape((rows, b), f32), shape((rows, k), f32), shape((b, k), f32), lam, w_rows),
        "streamed cached update": lambda: bcd._cached_block_update_fn(
            mesh, axis, precision, True, fold).lower(
            shape((rows, b), f32), shape((b, b), f32), shape((rows, k), f32),
            shape((b, k), f32), w_rows),
        **{name: lambda keep=keep: kernel_ridge._block_solve_fn(
            mesh, axis, kernel_ridge._precision(), fold, 8, keep, 3).lower(
            shape((rows, 4), f32), shape((rows, 3), f32), shape((), f32),
            shape((), jnp.int32), shape((6,), jnp.int32), GaussianKernelGenerator(0.5))
           for name, keep in (("kernel solver", 0), ("kernel solver, blocks kept", 3),
                              ("kernel solver, some blocks kept", 2))},
    }


# On the tests' mesh of 8 each program's reductions across it carry their
# ``coll.`` scope too (``sharded_rowsum``: the grams under ``coll.gram``,
# Aᵀ R and the kernel's Kᵀ α under ``coll.atr``), innermost on the path.
SOLVER_SCOPES = {
    "stack": {"solver.stack"},
    "factor": {"solver.gram", "solver.cholesky", "solver.inverse", "coll.gram"},
    "cached epochs": {"solver.update", "coll.atr"},
    "uncached epochs": {"solver.update", "solver.gram", "solver.cholesky", "solver.inverse",
                        "coll.gram", "coll.atr"},
    "streamed first epoch": {"solver.update", "solver.gram", "solver.cholesky",
                             "solver.inverse", "coll.gram", "coll.atr"},
    "streamed cached update": {"solver.update", "coll.atr"},
    "kernel solver": {"krr.generate", "krr.reduce", "krr.factor", "krr.solve", "coll.atr"},
    "kernel solver, blocks kept": {
        "krr.generate", "krr.fetch", "krr.reduce", "krr.factor", "krr.solve", "coll.atr"},
    "kernel solver, some blocks kept": {
        "krr.generate", "krr.fetch", "krr.reduce", "krr.factor", "krr.solve", "coll.atr"},
}


@pytest.mark.parametrize("phase", sorted(SOLVER_SCOPES))
def test_the_device_scopes_reach_the_compiled_programs_op_names(phase):
    """A ``jax.named_scope`` at the site is a segment of the compiled
    operations' ``op_name``, inside ``shard_map`` and inside a scan's body
    alike: the path a TPU trace keeps as ``tf_op``. The scopes of a program
    are the ones its row of PERF.md's table names and no other; what has
    none is the arguments and the loops' own counters."""
    from keystone_tpu.utils.device_trace import NO_OP_NAME, UNSCOPED
    from keystone_tpu.utils.metrics import DEVICE_SCOPES

    lowered = _solver_lowerings()[phase]()
    assert "module @jit_local " in lowered.as_text()
    scopes, names = _compiled_scopes(lowered)
    found = set(scopes) - {UNSCOPED, NO_OP_NAME}
    assert found == SOLVER_SCOPES[phase] and found <= set(DEVICE_SCOPES)
    for name in names:
        segments = [s for s in name.split("/") if s in DEVICE_SCOPES]
        # What crosses the mesh is the innermost scope of its path, inside
        # the scope of what is summed.
        if segments and segments[-1].startswith("coll."):
            assert len(segments) >= 2 and not segments[-2].startswith("coll."), name
            segments.pop()
        assert not [s for s in segments if s.startswith("coll.")], name
        # A scope is entered once on a path but for the update's loops,
        # which hold the uncached body's gram and inverse.
        assert len(segments) <= 1 or segments[0] == "solver.update", name
    heavy = [n for n in names if n.endswith(("dot_general", "cholesky", "triangular_solve"))]
    assert bool(heavy) == (phase != "stack")
    assert all(set(n.split("/")) & set(DEVICE_SCOPES) for n in heavy)
    if phase == "uncached epochs":
        assert any("/while/body/" in n and n.endswith("solver.gram/dot_general")
                   for n in names)


def test_a_fused_chains_steps_are_scoped_by_stage_and_the_kernels_parts_inside(rng):
    """One scope a step of the chain's walk, named as the walk's host spans
    are (``node:``) from the stages' class names; the step in which the
    convolver took its rectifier and pooler joins the three, and the
    convolver's own parts lie inside it."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.nodes.images import (
        Convolver,
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )
    from keystone_tpu.nodes.stats.normalizer import L2Normalizer
    from keystone_tpu.utils.device_trace import stage_of
    from keystone_tpu.workflow import FusedTransformer
    from keystone_tpu.workflow.pipeline import _program

    bank = rng.normal(size=(16, 6, 6, 3)).astype(np.float32)
    chain = FusedTransformer([
        Convolver(bank, normalize_patches=10.0), SymmetricRectifier(alpha=0.25),
        Pooler(4, 4, mode="sum"), ImageVectorizer(), L2Normalizer()])
    assert chain.fused_stages == 3
    lowered = _program(chain._program_name()).lower(
        chain, jax.ShapeDtypeStruct((5, 13, 13, 3), jnp.float32))
    assert ("module @jit_apply_Convolver_SymmetricRectifier_Pooler_ImageVectorizer"
            "_L2Normalizer ") in lowered.as_text()
    scopes, names = _compiled_scopes(lowered)
    taken = "node:Convolver+SymmetricRectifier+Pooler"
    assert {"conv.patches", "conv.kernel", "conv.relayout", taken,
            "node:L2Normalizer"} <= set(scopes)
    for name in names:
        if any(part in name for part in ("conv.patches", "conv.kernel", "conv.relayout")):
            assert stage_of(name) == taken, name
    # A transformer that is a program by itself is its one stage.
    alone = L2Normalizer()
    lowered = _program(alone._program_name()).lower(
        alone, jax.ShapeDtypeStruct((5, 7), jnp.float32))
    assert "module @jit_apply_L2Normalizer " in lowered.as_text()
    scopes, _names = _compiled_scopes(lowered)
    assert "node:L2Normalizer" in scopes


def test_the_mixture_fits_loop_body_is_scoped_by_step():
    import jax
    import jax.numpy as jnp

    from keystone_tpu.nodes.learning.gmm import _fit_gmm

    lowered = _fit_gmm.lower(
        jax.ShapeDtypeStruct((64, 8), jnp.float32), jax.random.PRNGKey(0), k=4,
        max_iters=3, min_var=1e-4)
    assert "module @jit__fit_gmm " in lowered.as_text()
    scopes, names = _compiled_scopes(lowered)
    assert {"gmm.estep", "gmm.mstep"} <= set(scopes)
    products = [n for n in names if "/while/body/" in n and n.endswith("dot_general")
                and "jit(_fit_kmeans)" not in n]
    assert products and all("gmm.estep" in n or "gmm.mstep" in n for n in products)


def test_the_kernel_solvers_program_keeps_the_name_too():
    """The block Gauss-Seidel of ``nodes/learning/kernel_ridge.py`` is a
    solver's program to the same three readers (``cifar-kernel-fit``): it
    carries ``jit_local`` until a ``benchmark`` PR renames both."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.linalg import RowMatrix
    from keystone_tpu.nodes.learning import GaussianKernelGenerator, kernel_ridge
    from keystone_tpu.utils.mesh import fold_blocks

    mesh, axis = RowMatrix.from_array(np.zeros((16, 4), np.float32)).mesh, config.data_axis
    rows, f32, shape = 16 * mesh.shape[axis], jnp.float32, jax.ShapeDtypeStruct
    lowered = kernel_ridge._block_solve_fn(
        mesh, axis, kernel_ridge._precision(), fold_blocks(mesh.shape[axis]), 8).lower(
        shape((rows, 4), f32), shape((rows, 3), f32), shape((), f32),
        shape((), jnp.int32), shape((6,), jnp.int32), GaussianKernelGenerator(0.5))
    assert "module @jit_local " in lowered.as_text()
