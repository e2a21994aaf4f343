"""cifar-fit at tiny widths on the CPU: a whole run through the harness's
functions, the run with each planted fault underneath (`FAULTS` of
configs/cifar-random-patch-10k-control.py, which reads the same faults on
the chip at full width), the control one precision step down wired as the
chip run wires it, the arithmetic against hand counts, and the three
per-layer metrics the cell brings. Counts and `correct` only, never a
speed."""

import importlib.util
import json
import os

import pytest

import harness
import work

CELL = "cifar-fit"
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tiny-cifar.json")) as f:
    _tiny = json.load(f)
OVERRIDES = {"sizes": _tiny["sizes"][CELL], "limits": _tiny["limits"]}


def _faults():
    spec = importlib.util.spec_from_file_location(
        "cifar_random_patch_10k_control",
        os.path.join(harness.HERE, "configs", "cifar-random-patch-10k-control.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.FAULTS


def _run(monkeypatch, plant, trace=False, seed=2200000003):
    adapter = harness.load_cell(CELL)["adapter"]

    class Planted:
        """The adapter with its fit replaced; the rest is the adapter's."""

        def __getattr__(self, name):
            return getattr(adapter, name)

        def fit(self, data, sizes):
            return plant(adapter, data, sizes)

    monkeypatch.setattr(harness, "load_adapter", lambda file_name: Planted())
    return harness.run_cell(CELL, seed, 0.3, trace, need_tpu=False, overrides=OVERRIDES)


def test_run_is_correct_and_well_formed():
    result = harness.run_cell(CELL, 2200000001, 0.5, False, need_tpu=False,
                              overrides=OVERRIDES)
    line = json.loads(json.dumps(result))  # what run.py prints
    assert line["correct"] is True, line["compared"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"fit_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["fits"]["ends_s"][-1] >= 0.5  # the window ran its length
    assert set(line["compared"]) == {"filters_gap", "features_gap", "scores_gap",
                                     "widths_off"}


# Which compared number has to catch which fault.
CAUGHT_BY = {
    "patch_normalisation_left_out": "filters_gap",
    "a_block_of_filters_zeroed": "features_gap",
    "a_pooling_window_one_short": "features_gap",
    "the_scaler_left_out": "scores_gap",
    "the_ragged_block_left_unsolved": "scores_gap",
    "half_the_batch": "filters_gap",
}


@pytest.mark.parametrize("fault", sorted(CAUGHT_BY))
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    result = _run(monkeypatch, _faults()[fault])
    assert result["correct"] is False
    caught = result["compared"][CAUGHT_BY[fault]]
    assert caught["value"] > 1.5 * caught["limit"], result["compared"]


def test_the_sound_fit_passes_the_same_way(monkeypatch):
    result = _run(monkeypatch, lambda adapter, data, sizes: adapter.fit(data, sizes))
    assert result["correct"] is True, result["compared"]


def test_same_seed_same_inputs():
    spec = harness.load_cell(CELL, OVERRIDES)
    make = spec["adapter"].make_data
    a, b = make(7, spec["sizes"]), make(7, spec["sizes"])
    c = make(2**31 + 7, spec["sizes"])
    assert (a["x"] == b["x"]).all() and (a["y"] == b["y"]).all()
    assert not (a["x"] == c["x"]).all()
    assert a["x"].shape == (160, 13, 13, 3) and a["x_held_out"].shape == (24, 13, 13, 3)
    assert float(a["x"].min()) >= 0.0 and float(a["x"].max()) <= 255.0
    assert set(a["y"].tolist()) == set(range(5))


def test_the_reference_draws_by_the_stated_rule():
    """`draw_indices` is the configuration's rule, and the program's."""
    import numpy as np

    spec = harness.load_cell(CELL, OVERRIDES)
    image, top, left, chosen = spec["adapter"].draw_indices(11, 160, spec["sizes"])
    rng = np.random.default_rng(11)
    assert (image == rng.integers(0, 160, size=2000)).all()
    assert (top == rng.integers(0, 8, size=2000)).all()
    assert (left == rng.integers(0, 8, size=2000)).all()
    assert (chosen == np.random.default_rng(12).choice(2000, size=64, replace=False)).all()


def test_the_control_reads_through_the_same_comparison():
    """The wiring of the chip's control run (tools/control.py): the reference
    one step down, put in the program's place, against the reference. On a
    CPU both are exact float32, so the gaps read nought; on the chip they
    are the limits' upper readings."""
    import sys

    sys.path.insert(0, os.path.join(harness.HERE, "tools"))
    import control

    spec = harness.load_cell(CELL, OVERRIDES)
    adapter, sizes = spec["adapter"], spec["sizes"]
    data = adapter.make_data(5, sizes)
    reference = adapter.reference(data, sizes, {}, "highest")
    lower = adapter.reference(data, sizes, {}, control.STEP_DOWN["highest"])
    assert set(control.gaps(lower, reference)) == {"filters_gap", "features_gap", "scores_gap"}
    assert reference["features"].shape == (24, 512) and reference["scores"].shape == (24, 5)
    assert reference["filters"].shape == (64, 109)


def test_canonical_work_hand_counts():
    """flops() and bytes_moved() of the configuration at its published
    sizes, against ISSUE 32's arithmetic."""
    spec = harness.load_cell(CELL)
    sizes, adapter = spec["sizes"], spec["adapter"]
    n, F, d, b, k = sizes["rows"], 10000, 80000, 4096, 10
    assert adapter.expected_facts(sizes) == {
        "feature_dim": d, "block_size": b, "blocks": 20, "classes": k, "filters": F}
    assert sizes["feature_dim"] == d
    f = adapter.flops(sizes, work)
    assert f["convolution"] == 2 * n * 729 * 108 * F

    def visits(width):  # a gram, a Cholesky, an inverse, three gemms and a product a block
        return (2 * n * width**2 + width**3 / 3 + 2 * width**3
                + 3 * 2 * n * width * k + 2 * width**2 * k)

    assert f["solver"] == pytest.approx(19 * visits(4096) + visits(2176))
    moved = adapter.bytes_moved(sizes, work)
    assert moved["convolution"] == 4 * (n * 3072 + 108 * F + F + n * 8 * F)
    assert moved["solver"] == work.bcd_bytes(n, 19 * b, k, b, 1) + work.bcd_bytes(n, 2176, k, 2176, 1)
    # The roofline the new metric reads: compute-bound on a v5e, and at
    # float32 HIGHEST (six bf16 passes) at most a sixth of it.
    least, bound = work.roofline_seconds(
        f["convolution"], moved["convolution"], work.chip_peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(f["convolution"] / 197e12)


def test_traced_run_holds_the_span_metrics(monkeypatch):
    """`conv_args_gib` is a count: the folded bank and the bias, to the byte."""
    result = _run(monkeypatch, lambda adapter, data, sizes: adapter.fit(data, sizes),
                  trace=True, seed=2200000009)
    metrics = result["metrics"]
    assert metrics["conv_args_gib"]["value"] == ((64 * 108 + 64) * 4) / 2**30
    assert metrics["filters_fit_ms"]["value"] > 0
    assert "conv_roofline" not in metrics  # no device plane on a CPU
    assert result["fits"]["count"] == 2  # the workload's traced_fits
