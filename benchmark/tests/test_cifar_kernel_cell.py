"""cifar-kernel-fit at tiny widths on the CPU: a whole run through the
harness's functions, the run with each planted fault underneath (`FAULTS`
of configs/cifar-random-patch-kernel-control.py, which reads the same faults
on the chip at full width), the control one precision step down wired as
the chip run wires it, the arithmetic against hand counts, and the two
per-layer metrics the cell brings. Counts and `correct` only, never a
speed."""

import importlib.util
import json
import os

import pytest

import harness
import work

CELL = "cifar-kernel-fit"
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tiny-cifar-kernel.json")) as f:
    _tiny = json.load(f)
OVERRIDES = {"sizes": _tiny["sizes"][CELL], "limits": _tiny["limits"]}


def _faults():
    spec = importlib.util.spec_from_file_location(
        "cifar_random_patch_kernel_control",
        os.path.join(harness.HERE, "configs", "cifar-random-patch-kernel-control.py"))
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
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["fits"]["ends_s"][-1] >= 0.5  # the window ran its length
    assert line["fits"]["window_compiles"] == 0  # a second fit compiles nothing
    assert set(line["compared"]) == {"features_gap", "alpha_gap", "scores_gap",
                                     "widths_off"}


# Every fault is planted in the solve, so the dual weights have to catch it.
@pytest.mark.parametrize("fault", [
    "the_diagonal_block_term_left_out", "lam_left_off_the_diagonal",
    "the_last_epoch_one_block_short", "the_ragged_block_left_unsolved",
    "one_epoch_too_few"])
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    assert set(_faults()) == {
        "the_diagonal_block_term_left_out", "lam_left_off_the_diagonal",
        "the_last_epoch_one_block_short", "the_ragged_block_left_unsolved",
        "one_epoch_too_few"}
    result = _run(monkeypatch, _faults()[fault])
    assert result["correct"] is False
    for caught in (result["compared"]["alpha_gap"], result["compared"]["scores_gap"]):
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


def test_the_control_reads_through_the_same_comparison():
    """The wiring of the chip's control run (tools/control.py): the reference
    one step down, put in the program's place, against the reference; its
    `alpha` is its solve on the fit's own features, as the reference's is.
    On a CPU both are exact float32, so the gaps read nought; on the chip
    they are the limits' upper readings."""
    import sys

    sys.path.insert(0, os.path.join(harness.HERE, "tools"))
    import control

    spec = harness.load_cell(CELL, OVERRIDES)
    adapter, sizes = spec["adapter"], spec["sizes"]
    data = adapter.make_data(5, sizes)
    answers = adapter.answers(adapter.fit(data, sizes), data, sizes)
    reference = adapter.reference(data, sizes, answers, "highest")
    lower = adapter.reference(data, sizes, answers, control.STEP_DOWN["highest"])
    assert set(control.gaps(lower, reference)) == {"features_gap", "alpha_gap", "scores_gap"}
    assert set(control.gaps(answers, reference, True)) == {
        "features_gap", "alpha_gap", "scores_gap"}
    assert reference["features"].shape == (24, 512) and reference["scores"].shape == (24, 5)
    assert reference["alpha"].shape == (160, 5)
    # Without the fit's features the reference still stands on its own.
    assert "alpha" not in adapter.reference(data, sizes, {}, "highest")


def test_canonical_work_hand_counts():
    """flops() and bytes_moved() of the configuration at its published
    sizes, against ISSUE 34's arithmetic."""
    spec = harness.load_cell(CELL)
    sizes, adapter = spec["sizes"], spec["adapter"]
    n, F, d, b, k, epochs = 50000, 512, 4096, 4096, 10, 3
    assert adapter.expected_facts(sizes) == {
        "feature_dim": d, "rows": n, "block_size": b, "blocks": 13, "classes": k,
        "filters": F}
    assert sizes["feature_dim"] == d and sizes["rows"] == n
    f = adapter.flops(sizes, work)
    assert f["convolution"] == 2 * n * 729 * 108 * F

    def visit(width):  # a Cholesky and a solve against it
        return width**3 / 3 + 2 * width**2 * k

    assert f["solver"] == pytest.approx(
        epochs * (2 * n * n * d + 2 * n * n * k + 12 * visit(4096) + visit(848)))
    # Generation is all but all of it: 61.4 of a fit's 66.5 TFLOP.
    assert epochs * 2 * n * n * d / f["solver"] > 0.98
    assert 66e12 < sum(f.values()) < 67e12
    moved = adapter.bytes_moved(sizes, work)
    assert moved["convolution"] == 4 * (n * 3072 + 108 * F + F + n * 8 * F)
    assert moved["solver"] == 4 * epochs * (13 * n * d + 3 * n * k)
    # Compute-bound on a v5e; at float32 HIGHEST (six bf16 passes) at most
    # a sixth of the roofline.
    least, bound = work.roofline_seconds(
        f["solver"], moved["solver"], work.chip_peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(f["solver"] / 197e12)


def test_traced_run_holds_the_span_metrics(monkeypatch):
    """`kernel_blocks_per_fit` is a count: epochs x blocks."""
    result = _run(monkeypatch, lambda adapter, data, sizes: adapter.fit(data, sizes),
                  trace=True, seed=2200000009)
    metrics = result["metrics"]
    assert metrics["kernel_blocks_per_fit"] == {"value": 12.0, "unit": "count"}
    assert metrics["krr_host_ms"]["value"] > 0
    assert metrics["compiles_per_fit"]["value"] == 0
    assert "solver_roofline" not in metrics  # no device plane on a CPU
    assert result["fits"]["count"] == 2  # the workload's traced_fits
