"""The readers of the program's span ring (`spanreaders.py`) on a ring
built by hand, where every number can be checked; `tools/host_gaps.py` on
hand-built spans and on one small trace recorded on a v5e with the spans in
it (tools/record_trace.py, as `tiny-imagenet-fit.xplane.pb.gz` was); and a
traced whole run at tiny widths on the CPU, whose result line has to hold
the five metrics (no device plane there: the trace's readers give nothing)."""

import gzip
import importlib.util
import json
import os
import shutil

import pytest

import harness
import spanreaders as sr
from conftest import TINY, TINY_LIMITS

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def _load_host_gaps():
    path = os.path.join(os.path.dirname(HERE), "tools", "host_gaps.py")
    spec = importlib.util.spec_from_file_location("benchmark_host_gaps", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _fit(first_id, t0, fetch_bytes):
    """One fit's spans, in ms from ``t0``: the root 1000 long, of which
    fetch 200, sample 100 and a node 500 are named; in the node a re-trace
    of 90 with a primitive's trace of 10 nested in it, a lowering of 50, a
    compile of 150 and a solver span of 100."""
    def span(i, parent, name, start, end, **args):
        return {"id": first_id + i,
                "parent_id": None if parent is None else first_id + parent,
                "root_id": first_id, "name": name, "cat": "t",
                "start_ns": (t0 + start) * MS, "dur_ns": (end - start) * MS,
                "tid": 1, "thread": "main", "args": args}
    return [
        span(1, 0, "fisher.fetch", 100, 300, bytes=fetch_bytes),
        span(2, 0, "fisher.sample", 300, 400),
        span(5, 3, "jax.trace", 420, 430, fun_name="multiply"),
        span(4, 3, "jax.trace", 410, 500, fun_name="apply_batch"),
        span(6, 3, "jax.lower", 500, 550, fun_name="jit(apply_batch)"),
        span(7, 3, "jax.compile", 550, 700, fun_name="jit(apply_batch)", cache_hit=False),
        span(8, 3, "solver.factor", 700, 800),
        span(3, 0, "node:X", 400, 900),
        span(0, None, "fit", 0, 1000),
    ]


def _ring():
    return _fit(100, 0, 7 * 2**30) + _fit(200, 5000, 2**30) + _fit(300, 9000, 3 * 2**30)


def test_readers_on_a_hand_built_ring():
    ctx = {"fits": 2, "fit_s": 1.5}
    w = sr.window(ctx, ring=_ring())  # the last two fits: the first is not read
    assert [r["id"] for r in w["roots"]] == [200, 300]
    assert sr.span_self_ms(ctx, "fisher.fetch") == pytest.approx(200)
    assert sr.span_self_ms(ctx, "fisher.sample") == pytest.approx(100)
    assert sr.span_attr_gib(ctx, "fisher.fetch", "bytes") == pytest.approx(2.0)
    # The nested trace counts once: 410..700 is covered, not 90+10+50+150.
    assert sr.retrace_ms(ctx) == pytest.approx(290)
    # The node keeps what neither the jax.* records nor the solver covers.
    assert sr.span_self_ms(ctx, "node:X") == pytest.approx(500 - 290 - 100)
    assert sr.span_self_ms(ctx, "jax.trace") == pytest.approx(90)
    assert sr.span_self_ms(ctx, "jax.compile") == pytest.approx(150)
    # The root names 800 of its 1000.
    assert sr.span_self_ms(ctx, "fit") == pytest.approx(200)
    assert sr.span_coverage(ctx) == pytest.approx(80.0)
    # Every self time together is the roots' time: nothing counted twice.
    assert sum(w["self_ns"].values()) == pytest.approx(2 * 1000 * MS)
    assert sr.span_self_ms(ctx, "absent") is None
    notes = "\n".join(ctx["notes"])
    assert "fisher.fetch 200.0" in notes
    assert "0.500 s a fit" in notes  # fit_s 1.5 less the root's 1.0
    assert "jit(apply_batch) under node:X x2: 0 from the cache, 2 compiled" in notes
    assert len(ctx["notes"]) == 3  # written once, however many readers ran


def test_fewer_roots_than_fits_reads_nothing():
    ctx = {"fits": 4}
    assert sr.window(ctx, ring=_ring()) is None
    for value in (sr.span_self_ms(ctx, "fisher.fetch"), sr.retrace_ms(ctx),
                  sr.span_attr_gib(ctx, "fisher.fetch"), sr.span_coverage(ctx)):
        assert value is None
    assert ctx["notes"] == ["host spans: 3 'fit' roots in the ring for 4 fits: nothing read"]
    # A ring of the time before ids (the parent's) has no root to count.
    old = [{k: v for k, v in s.items() if k not in ("id", "parent_id", "root_id")}
           for s in _ring()]
    assert sr.window({"fits": 1}, ring=old) is None


def test_innermost_span_by_stretch():
    hg = _load_host_gaps()
    spans = [("ks:fit", 0, 100), ("ks:fisher.fetch", 10, 40), ("ks:node:X", 50, 90),
             ("ks:jax.compile", 49, 70)]  # laid over from another clock: starts early
    assert hg.innermost(spans) == [
        (0, 10, "ks:fit"), (10, 40, "ks:fisher.fetch"), (40, 49, "ks:fit"),
        (49, 70, "ks:jax.compile"), (70, 90, "ks:node:X"), (90, 100, "ks:fit")]
    segments = hg.innermost(spans[:3])
    assert hg._overlaps(segments, [s[0] for s in segments], 30, 120) == {
        "ks:fisher.fetch": 10, "ks:fit": 20, "ks:node:X": 40, hg.NO_SPAN: 20}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    src = os.path.join(HERE, "data", "tiny-imagenet-fit-spans.xplane.pb.gz")
    dst = str(tmp_path_factory.mktemp("trace") / "tiny-spans.xplane.pb")
    with gzip.open(src, "rb") as fi, open(dst, "wb") as fo:
        shutil.copyfileobj(fi, fo)
    return dst


def test_host_gaps_on_a_recorded_trace(recorded):
    hg = _load_host_gaps()
    names = {n for n, _s, _e in hg.read_host_spans(recorded)}
    assert {"bench.fit", "ks:fit", "ks:pipeline.fit", "ks:fisher.describe", "ks:fisher.fetch",
            "ks:fisher.flatten", "ks:fisher.sample", "ks:pca.fit", "ks:gmm.fit", "ks:solver.factor"} <= names
    table = hg.attribute(recorded)
    assert table["fits"] == 2
    # Every idle second has one owner: the owners' seconds are the window
    # less the busy time, and so are the gaps'.
    idle = table["window_s"] / 2 - table["busy_s_a_fit"]
    assert table["idle_s_a_fit"] == pytest.approx(idle, rel=1e-9)
    whole = hg.attribute(recorded, top=10**6)
    assert sum(s for _n, s in whole["idle_by_span"]) == pytest.approx(idle, rel=1e-9)
    assert sum(g["s_a_fit"] for g in whole["gaps"]) == pytest.approx(idle, rel=1e-9)
    # The program's spans own nearly all of it: what no span covers is the
    # harness's own wait for the weights, after the root has closed.
    owners = dict(whole["idle_by_span"])
    assert all(n.startswith("ks:") or n == hg.NO_SPAN for n in owners)
    assert owners.get(hg.NO_SPAN, 0.0) < 0.2 * idle
    assert table["gaps"][0]["spans"][0][0].startswith("ks:")


def test_traced_run_on_the_cpu_holds_the_five_span_metrics():
    result = harness.run_cell(
        "imagenet-fit", 2200000003, 0.5, True, need_tpu=False,
        overrides={"sizes": TINY["imagenet-fit"], "limits": TINY_LIMITS})
    line = json.loads(json.dumps(result))  # what run.py prints
    assert line["correct"] is True, line["compared"]
    metrics = line["metrics"]
    # No device plane on a CPU: the trace's readers give nothing; the
    # counters and the ring's readers give numbers.
    assert {"compiles_per_fit", "host_fetch_ms", "host_fetch_gib", "host_sample_ms",
            "retrace_ms", "span_coverage"} <= set(metrics)
    assert not {"device_idle", "solver_roofline", "factor_ms", "featurize_ms"} & set(metrics)
    sizes = TINY["imagenet-fit"]
    keypoints = 25  # a 32 px image at step 4, bin 4
    fetched = (sizes["rows"] * keypoints * (128 + 96)
               + 2 * sizes["descriptor_sample"] * sizes["pca_dims"]) * 4
    assert metrics["host_fetch_gib"]["value"] == pytest.approx(fetched / 2**30, rel=1e-12)
    assert metrics["host_fetch_ms"]["value"] > 0 and metrics["host_sample_ms"]["value"] > 0
    assert metrics["retrace_ms"]["value"] > 0
    assert 50.0 < metrics["span_coverage"]["value"] <= 100.0
    assert any(n.startswith("host spans, self ms a fit") for n in line["notes"])
    assert any(n.startswith("wait for the device after the fit returned") for n in line["notes"])
