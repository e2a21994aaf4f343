"""timit-fit at tiny widths on the CPU: a whole run through the harness's
functions, the run with each planted fault underneath (`FAULTS` of
configs/timit-cosine-rf-control.py, which reads the same faults on the chip
at full width), the arithmetic against hand counts, and the three
per-layer metrics the cell brings. Counts and `correct` only, never a
speed."""

import importlib.util
import json
import os

import pytest

import harness
import work

CELL = "timit-fit"
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tiny-timit.json")) as f:
    _tiny = json.load(f)
OVERRIDES = {"sizes": _tiny["sizes"][CELL], "limits": _tiny["limits"]}


def _faults():
    spec = importlib.util.spec_from_file_location(
        "timit_cosine_rf_control",
        os.path.join(harness.HERE, "configs", "timit-cosine-rf-control.py"))
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
    assert set(line["compared"]) == {"features_gap", "scores_gap", "W_gap", "b_gap",
                                     "widths_off"}


# Which compared number has to catch which fault.
CAUGHT_BY = {
    "a_cosine_block_zeroed": "features_gap",
    "an_epoch_left_out": "scores_gap",
    "a_block_left_unsolved": "scores_gap",
    "one_block_drawn_for_all": "W_gap",
    "the_bandwidth_a_tenth_off": "W_gap",
    "the_phases_on_half_the_circle": "b_gap",
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
    assert a["x"].shape == (512, 24) and a["x_held_out"].shape == (64, 24)


def test_canonical_work_hand_counts():
    """flops() and bytes_moved() of the configuration at its published
    sizes, against ISSUE 28's arithmetic at 40 blocks."""
    spec = harness.load_cell(CELL)
    sizes, adapter = spec["sizes"], spec["adapter"]
    n, m, d, b, k, epochs = 4096, 440, 163840, 4096, 147, 5
    assert adapter.expected_facts(sizes) == {
        "feature_dim": d, "block_size": b, "blocks": 40, "classes": k}
    f = adapter.flops(sizes, work)
    assert f["random_features"] == 2 * n * m * d
    once = 2 * n * b * b + b**3 / 3 + 2 * b**3
    visit = 3 * 2 * n * b * k + 2 * b * b * k
    assert f["solver"] == pytest.approx(40 * (once + epochs * visit))
    assert 1.6e13 < sum(f.values()) < 1.7e13
    moved = adapter.bytes_moved(sizes, work)
    assert moved["random_features"] == 4 * (n * m + m * d + d + n * d)
    assert moved["solver"] == work.bcd_bytes(n, d, k, b, epochs)
    # The roofline the new metric reads: memory-bound on a v5e.
    least, bound = work.roofline_seconds(
        f["random_features"], moved["random_features"], work.chip_peaks("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(2.980e9 / 819e9, rel=1e-3)


def test_traced_run_holds_the_span_metrics(monkeypatch):
    """`rf_args_gib` is a count: the chain's arrays, to the byte."""
    result = _run(monkeypatch, lambda adapter, data, sizes: adapter.fit(data, sizes),
                  trace=True, seed=2200000009)
    metrics = result["metrics"]
    m, d = 24, 128
    assert metrics["rf_args_gib"]["value"] == ((m * d + d) * 4 + 2 * m * 4) / 2**30
    assert metrics["rf_host_ms"]["value"] > 0
    assert "rf_roofline" not in metrics  # no device plane on a CPU
    assert result["fits"]["count"] == 4  # the workload's traced_fits
