"""The reduction from a profiler trace to per-layer numbers: on planes
built by hand, where every number can be checked, and on one small trace
recorded on a v5e (tools/record_trace.py: imagenet-fit at the tiny widths
of data/tiny.json, two traced fits)."""

import gzip
import os
import shutil

import pytest

import reducers
import tracereduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
SOLVER = ["jit_local", "jit__batched_spd_inv"]


def _planes():
    chol = '%custom-call.3 = f32[2,128,128] custom-call(f32[2,128,128] %x), custom_call_target="Cholesky"'
    ops = [
        ("%fusion.1 = f32[8] fusion(f32[8] %a)", 10, 30),       # in jit_apply_batch
        ("%while.2 = (s32[]) while(%t)", 40, 80),               # in jit_local(1): 40 long
        ("%fusion.7 = f32[8] fusion(f32[8] %b)", 45, 55),       #   child, 10
        (chol, 60, 70),                                          #   child, 10
        ("%fusion.9 = f32[8] fusion(f32[8] %c)", 120, 150),     # in jit_local(2), no Cholesky
    ]
    modules = [("jit_apply_batch(11)", 10, 30), ("jit_local(1)", 40, 80), ("jit_local(2)", 120, 150)]
    host = [("bench.fit", 0, 100), ("bench.fit", 100, 200), ("something_else", 0, 500)]
    return [("/device:TPU:0", [(tr.MODULES_LINE, modules), (tr.OPS_LINE, ops)]),
            ("/host:CPU", [("python3", host)])]


def test_hand_built_planes():
    s = tr.reduce_planes(_planes())
    assert s["window_s"] == pytest.approx(200e-9)
    assert s["busy_s"] == pytest.approx((20 + 40 + 30) * 1e-9)
    ops = {(m, o): (sec, tags) for m, o, sec, tags in s["ops"]}
    # The while keeps only what its children do not cover.
    assert ops[("jit_local", "while.2")][0] == pytest.approx(20e-9)
    assert ops[("jit_local", "fusion.7")] == (pytest.approx(10e-9), ["Cholesky"])
    assert ops[("jit_local", "custom-call.3[Cholesky]")][0] == pytest.approx(10e-9)
    assert ops[("jit_local", "fusion.9")] == (pytest.approx(30e-9), [])
    assert sum(sec for sec, _ in ops.values()) == pytest.approx(s["busy_s"])
    assert s["fit_modules_s"][0] == {"jit_apply_batch": pytest.approx(20e-9),
                                     "jit_local": pytest.approx(40e-9)}
    assert s["fit_modules_s"][1] == {"jit_local": pytest.approx(30e-9)}
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["bench.fit[0]: after jit_local, before jit_local"] == pytest.approx(40e-9)
    assert gaps["bench.fit[0]: after jit_apply_batch, before jit_local"] == pytest.approx(10e-9)
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_readers_on_hand_built_planes():
    ctx = {"trace": tr.reduce_planes(_planes()), "fits": 2}
    ms = reducers.device_ms_per_fit
    assert ms(ctx, modules=SOLVER) == pytest.approx(70e-6 / 2)
    assert ms(ctx, modules=SOLVER, run_has="Cholesky") == pytest.approx(40e-6 / 2)
    assert ms(ctx, exclude_modules=SOLVER) == pytest.approx(20e-6 / 2)
    assert reducers.device_idle(ctx) == pytest.approx(100 * (1 - 90 / 200))
    # Nothing to read: nothing returned, never a 0.
    assert ms(ctx, modules=["jit_absent"]) is None
    assert reducers.device_idle({"trace": None}) is None
    assert reducers.roofline({"trace": None, "peaks": None}, "solver", SOLVER) is None


def test_a_trace_without_a_fit_span_is_an_error():
    planes = [p for p in _planes() if p[0] != "/host:CPU"]
    with pytest.raises(ValueError):
        tr.reduce_planes(planes)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    src = os.path.join(HERE, "data", "tiny-imagenet-fit.xplane.pb.gz")
    dst = str(tmp_path_factory.mktemp("trace") / "tiny.xplane.pb")
    with gzip.open(src, "rb") as fi, open(dst, "wb") as fo:
        shutil.copyfileobj(fi, fo)
    return tr.reduce_planes(tr.read_xplane(dst))


def test_recorded_trace(recorded):
    s = recorded
    assert 0 < s["busy_s"] < s["window_s"]
    assert sum(sec for _m, _o, sec, _t in s["ops"]) == pytest.approx(s["busy_s"], rel=1e-6)
    modules = {m for m, _o, _s, _t in s["ops"]}
    assert "jit_local" in modules and "jit__fit_gmm" in modules
    assert len(s["fit_modules_s"]) == 2
    first, second = (f["jit_local"] for f in s["fit_modules_s"])
    assert second == pytest.approx(first, rel=0.25)  # every fit does the whole work
    ctx = {"trace": s, "fits": 2}
    solver = reducers.device_ms_per_fit(ctx, modules=SOLVER)
    factor = reducers.device_ms_per_fit(ctx, modules=SOLVER, run_has="Cholesky")
    featurize = reducers.device_ms_per_fit(ctx, exclude_modules=SOLVER)
    assert 0 < factor < solver
    assert (solver + featurize) * 2 / 1e3 == pytest.approx(s["busy_s"], rel=1e-6)
    assert len(s["breakdown"]["device_ops"]) == 10 and len(s["breakdown"]["idle_gaps"]) == 10
