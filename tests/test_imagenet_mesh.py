"""The ImageNet SIFT/LCS Fisher-vector fit on a mesh (the benchmark's
``imagenet-sift-lcs-fv-64k-x4``), at tiny widths on the CPU's fake devices:
against its plain one-device reference, against the same fit on one device,
with rows that do not divide the mesh, with nothing of the feature matrix's
size whole on a device or on the host, with a second fit that compiles
nothing, and with the spans, scopes and counter the collectives bring.
Counts and `correct` only, never a speed."""

import json
import os
import sys

import jax
import numpy as np
import pytest

from keystone_tpu.config import config
from keystone_tpu.utils.mesh import (
    default_mesh,
    layout_of_array,
    set_default_mesh,
)
from keystone_tpu.utils.metrics import (
    DEVICE_SCOPES,
    CompileEventCounter,
    recorded_tracer,
    reset_tracer,
    sharding_counters,
)
from keystone_tpu.workflow import PipelineEnv, Transformer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "imagenet-fit-4chip"

with open(os.path.join(BENCH, "tests", "data", "tiny-imagenet-4chip.json")) as f:
    TINY = json.load(f)


@pytest.fixture(scope="module")
def harness():
    sys.path[:0] = [BENCH, os.path.join(BENCH, "configs")]
    try:
        import harness

        yield harness
    finally:
        del sys.path[:2]


def _mesh(width: int):
    # conftest's fresh_env drops the narrow mesh again after the test.
    set_default_mesh(default_mesh(devices=jax.devices()[:width]))


def _sizes(rows: int, width: int) -> dict:
    return dict(TINY["sizes"], rows=rows, shards=width, rows_per_shard=-(-rows // width))


def _spec(harness, rows: int, width: int):
    return harness.load_cell(CELL, {"sizes": _sizes(rows, width), "limits": TINY["limits"]})


def _fit_and_answer(harness, rows: int, width: int, seed: int):
    PipelineEnv.reset()
    _mesh(width)
    spec = _spec(harness, rows, width)
    adapter, sizes = spec["adapter"], spec["sizes"]
    # The one-chip configuration's generator: the same images whatever the
    # width, and any number of rows (the cell's own makes a shard's a call).
    data = adapter.base.make_data(seed, sizes)
    return spec, data, adapter.answers(adapter.fit(data, sizes), data, sizes)


def _gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("width", [4, 8])
def test_the_fit_on_the_mesh_is_the_references(harness, width):
    """Every number the configuration limits, through the harness's own run:
    the fit on ``width`` devices against the one-device reference."""
    _mesh(width)
    result = harness.run_cell(
        CELL, 2200000011, 0.2, False, need_tpu=False,
        overrides={"sizes": _sizes(96, width), "limits": TINY["limits"]})
    assert result["correct"] is True, result["compared"]
    assert set(result["compared"]) == set(TINY["limits"]) | {"widths_off"}
    assert result["compared"]["widths_off"]["value"] == 0
    assert result["fits"]["window_compiles"] == 0


def test_rows_that_do_not_divide_the_mesh(harness):
    """98 rows on 4 devices: the chains pad two rows and trim them, the
    solver pads to its fold's blocks with zero rows of zero weight, and
    every statistic is the reference's over the 98 rows alone."""
    spec, data, answers = _fit_and_answer(harness, 98, 4, 2200000012)
    assert sharding_counters.get("pad_rows_added") > 0
    adapter = spec["adapter"]
    reference = adapter.reference(data, spec["sizes"], answers, "highest")
    answers.update(reference["measured"])
    checks = harness.compare(answers, reference, spec["limits"])
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    assert answers["facts"]["shards"] == 4 and answers["facts"]["rows_per_shard"] == 25


def test_the_mesh_and_one_device_agree_to_the_reductions_order(harness):
    """The same rows and seed on 4 devices and on 1. The descriptor sample
    is the same rows and the PCA one SVD of it on every device: equal. The
    mixtures' sums, the chains' products and the grams are made a shard at
    a time and combined in the canonical fold's order, so the mixture moves
    by float32 rounding (1e-6 of its means), the Fisher vectors by what
    their square roots and norms make of that (1.5e-5 over four seeds:
    limit 1e-4), and the scores by the ridge solve's condition number at
    lambda 1e-3 times that (2.9e-4 over four seeds: limit 2e-3, the size
    of the same fit's gap to its reference). Widths 4 and 8 read the same
    bits: the fold is the same sixteen blocks on both."""
    _spec4, _data, four = _fit_and_answer(harness, 96, 4, 2200000013)
    _spec1, _data, one = _fit_and_answer(harness, 96, 1, 2200000013)
    _spec8, _data, eight = _fit_and_answer(harness, 96, 8, 2200000013)
    for mesh, alone in zip(four["fitted"], one["fitted"]):
        np.testing.assert_array_equal(mesh["pca_components"], alone["pca_components"])
        assert _gap(mesh["gmm_means"], alone["gmm_means"]) <= 1e-5
    assert 0 < _gap(four["features"], one["features"]) <= 1e-4
    assert _gap(four["scores"], one["scores"]) <= 2e-3
    np.testing.assert_array_equal(four["scores"], eight["scores"])
    assert (one["facts"]["shards"], four["facts"]["shards"], eight["facts"]["shards"]) == (1, 4, 8)


@pytest.fixture
def traced_mesh_fit(harness, monkeypatch):
    """Two traced fits on 4 devices, the second with another seed: (the
    second fit's spans, what every ``batch_call`` of it was handed, the
    compile requests it made, the adapter, its sizes)."""
    PipelineEnv.reset()
    _mesh(4)
    spec = _spec(harness, 96, 4)
    adapter, sizes = spec["adapter"], spec["sizes"]
    handed = []
    batch_call = Transformer.batch_call

    def watching(self, X):
        handed.append(X)
        return batch_call(self, X)

    monkeypatch.setattr(config, "trace", True)
    reset_tracer()
    try:
        adapter.fit(adapter.make_data(2200000014, sizes), sizes)
        data = adapter.make_data(2200000015, sizes)
        monkeypatch.setattr(Transformer, "batch_call", watching)
        compiles = CompileEventCounter()
        adapter.fit(data, sizes)
        requests = compiles.count
        spans = recorded_tracer().spans()
    finally:
        reset_tracer()
    (root,) = [s for s in spans if s["name"] == "fit" and s.get("parent_id") is None][-1:]
    return [s for s in spans if s["root_id"] == root["id"]], handed, requests, adapter, sizes


def test_a_second_fit_with_another_seed_compiles_nothing(traced_mesh_fit):
    spans, _handed, requests, _adapter, _sizes_ = traced_mesh_fit
    assert requests == 0
    assert not [s for s in spans if s["name"].startswith("jax.")]
    (root,) = [s for s in spans if s["name"] == "fit"]
    assert root["args"]["closure_program_calls"] == 0


def test_nothing_of_the_feature_matrixs_size_is_whole_anywhere(traced_mesh_fit):
    """What every ``batch_call`` of the fit is handed: no host array beyond
    a few KB (the labels), and every device array that has the train rows
    lies row-sharded over the 4 devices. The two replicated arrays are the
    descriptor samples, which have the sample's rows, not the train rows'."""
    _spans, handed, _requests, _adapter, sizes = traced_mesh_fit
    assert not [x for x in handed if isinstance(x, np.ndarray) and x.nbytes > 4096]
    with_rows = [x for x in handed if isinstance(x, jax.Array) and x.shape[0] == sizes["rows"]]
    assert len(with_rows) >= 5  # four walks of the images, and the labels
    for x in with_rows:
        assert layout_of_array(x) is not None and layout_of_array(x).num_shards == 4
    samples = [x for x in handed if isinstance(x, jax.Array)
               and x.shape[0] == sizes["descriptor_sample"]]
    assert len(samples) == 2 and all(len(x.sharding.device_set) == 4 for x in samples)


def test_the_root_counts_the_collectives_and_names_the_mesh(traced_mesh_fit):
    """The `fit` root: over how many devices, how many rows each, and the
    bytes its reductions were handed, which is what the adapter counts from
    the sizes; `data.place` says the put was by shard."""
    spans, _handed, _requests, adapter, sizes = traced_mesh_fit
    (root,) = [s for s in spans if s["name"] == "fit"]
    assert root["args"]["shards"] == 4 and root["args"]["rows_per_shard"] == 24
    assert root["args"]["collective_bytes"] == adapter.collective_bytes(sizes)
    (place,) = [s for s in spans if s["name"] == "data.place"]
    assert place["args"]["sharded"] == 1 and place["args"]["rows"] == 96


def test_one_device_counts_no_collective(harness, monkeypatch):
    PipelineEnv.reset()
    _mesh(1)
    spec = _spec(harness, 96, 1)
    adapter, sizes = spec["adapter"], spec["sizes"]
    monkeypatch.setattr(config, "trace", True)
    reset_tracer()
    try:
        adapter.fit(adapter.make_data(2200000016, sizes), sizes)
        spans = recorded_tracer().spans()
    finally:
        reset_tracer()
    (root,) = [s for s in spans if s["name"] == "fit" and s.get("parent_id") is None]
    assert root["args"]["shards"] == 1 and root["args"]["collective_bytes"] == 0
    (place,) = [s for s in spans if s["name"] == "data.place"]
    assert place["args"]["sharded"] == 0


COLLECTIVE_SCOPES = ("coll.gram", "coll.atr", "coll.moments", "coll.em", "coll.sample")


def test_the_collectives_scopes_are_device_scopes():
    assert set(COLLECTIVE_SCOPES) <= set(DEVICE_SCOPES)


@pytest.mark.parametrize("scope", COLLECTIVE_SCOPES)
def test_every_reduction_across_the_mesh_is_under_its_scope(scope):
    """Each reduction's program, compiled for 4 devices: the operations that
    cross the mesh (the fold's `collective-permute`s, the sample's
    `all-reduce`) carry the scope in their `op_name`, and on one device
    nothing crosses."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.linalg import bcd
    from keystone_tpu.linalg.row_matrix import _col_sum_fn, _precision
    from keystone_tpu.nodes.learning.gmm import _fit_gmm
    from keystone_tpu.nodes.stats.samplers import _take_rows_sharded
    from keystone_tpu.utils.mesh import SpecLayout, fold_blocks

    def lowered(width):
        mesh = default_mesh(devices=jax.devices()[:width])
        fold, axis = fold_blocks(width), config.data_axis

        def arg(shape, spec, dtype=np.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

        if scope == "coll.gram":
            return bcd._fused_factor_fn(mesh, axis, _precision(), True, fold, 0).lower(
                arg((2, 64, 16), P(None, axis)), arg((), P()), arg((64,), P(axis)))
        if scope == "coll.atr":
            return bcd._fused_epochs_fn(mesh, axis, _precision(), True, 2, True, fold, 0).lower(
                arg((2, 64, 16), P(None, axis)), arg((2, 16, 16), P()), arg((64, 3), P(axis)),
                arg((2, 16, 3), P()), arg((), P()), arg((64,), P(axis)))
        if scope == "coll.moments":
            return _col_sum_fn(mesh, axis, fold).lower(arg((64, 16), P(axis)))
        layout = SpecLayout(mesh, axis)
        if scope == "coll.em":
            return _fit_gmm.lower(arg((640, 8), P()), arg((2,), P(), np.uint32), 4, 2, 1e-4,
                                  layout if width > 1 else None)
        return _take_rows_sharded(layout).lower(
            arg((64, 5, 8), P(axis)), arg((40,), P(), np.int32))

    def crossing(program):
        return [line for line in program.compile().as_text().splitlines()
                if " collective-permute(" in line or " all-reduce(" in line
                or " collective-permute-start(" in line or " all-reduce-start(" in line]

    on_four = crossing(lowered(4))
    assert on_four and all(scope + "/" in line for line in on_four), on_four[:2]
    # One device's sample is `_take_rows`, which this one never replaces.
    assert scope == "coll.sample" or not crossing(lowered(1))


@pytest.mark.parametrize("width,streams", [(4, False), (1, True)])
def test_the_fits_in_hbm_rule_counts_what_one_device_holds(monkeypatch, width, streams):
    """A feature matrix that is over half a device's memory whole and under
    it a shard: on the mesh it is solved where it lies, on one device it
    streams from the host. (Counted whole, `imagenet-fit-4chip`'s 8.6 GB
    went to the host and back a block a visit: 45 s a fit, PR 38.)"""
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.learning import block_least_squares as bls
    from keystone_tpu.utils import metrics

    _mesh(width)
    rng = np.random.default_rng(0)
    X = rng.normal(size=(256, 64)).astype(np.float32)  # 65,536 bytes
    Y = rng.normal(size=(256, 3)).astype(np.float32)
    monkeypatch.setattr(metrics, "device_hbm_bytes", lambda: 100_000)
    streamed = []
    sound = bls.block_coordinate_descent_streamed

    def watching(*args, **kwargs):
        streamed.append(1)
        return sound(*args, **kwargs)

    monkeypatch.setattr(bls, "block_coordinate_descent_streamed", watching)
    BlockLeastSquaresEstimator(block_size=32, num_iters=1, lam=1e-3).fit(X, Y)
    assert bool(streamed) is streams
