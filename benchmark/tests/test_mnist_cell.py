"""mnist-fft-fit at tiny widths on the CPU: a whole run through the
harness's functions, the run with each planted fault underneath (`FAULTS`
of configs/mnist-random-fft-200-control.py, which reads the same faults on
the chip at full width), the control one precision step down wired as the
chip run wires it, the arithmetic against hand counts, and the per-layer
metric the cell brings. Counts and `correct` only, never a speed."""

import importlib.util
import json
import os

import pytest

import harness
import work

CELL = "mnist-fft-fit"
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tiny-mnist.json")) as f:
    _tiny = json.load(f)
OVERRIDES = {"sizes": _tiny["sizes"][CELL], "limits": _tiny["limits"]}


def _faults():
    spec = importlib.util.spec_from_file_location(
        "mnist_random_fft_200_control",
        os.path.join(harness.HERE, "configs", "mnist-random-fft-200-control.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.FAULTS


@pytest.fixture
def two_of_three_kept(monkeypatch):
    """On the CPU every block fits the store: keep two of the three, so
    that kept blocks (two slots: a wrong one to read) and a block made
    again are read."""
    import sys

    if harness.ROOT not in sys.path:
        sys.path.insert(0, harness.ROOT)
    from keystone_tpu.linalg import bcd

    monkeypatch.setattr(bcd, "_blocks_kept", lambda *sizes, **kw: 2)
    bcd._generated_solve_fn.cache_clear()
    yield
    bcd._generated_solve_fn.cache_clear()


def _run(monkeypatch, plant, trace=False, seed=4200000003):
    adapter = harness.load_cell(CELL)["adapter"]

    class Planted:
        """The adapter with its fit replaced; the rest is the adapter's."""

        def __getattr__(self, name):
            return getattr(adapter, name)

        def fit(self, data, sizes):
            return plant(adapter, data, sizes)

    monkeypatch.setattr(harness, "load_adapter", lambda file_name: Planted())
    return harness.run_cell(CELL, seed, 0.3, trace, need_tpu=False, overrides=OVERRIDES)


def test_run_is_correct_and_well_formed(two_of_three_kept):
    result = harness.run_cell(CELL, 4200000001, 0.5, False, need_tpu=False,
                              overrides=OVERRIDES)
    line = json.loads(json.dumps(result))  # what run.py prints
    assert line["correct"] is True, line["compared"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"fit_s", "setup_s"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["fits"]["window_compiles"] == 0  # a second fit compiles nothing
    assert set(line["compared"]) == {"signs_gap", "features_gap", "weights_gap",
                                     "scores_gap", "widths_off"}


_IN_THE_SOLVE = ("a_regenerated_block_from_the_wrong_signs",
                 "a_kept_block_read_at_the_wrong_index", "one_epoch_too_few")


@pytest.mark.parametrize("fault", _IN_THE_SOLVE + ("the_branches_stacked_in_reverse",))
def test_a_planted_fault_is_not_correct(fault, monkeypatch, two_of_three_kept):
    assert set(_faults()) == set(_IN_THE_SOLVE) | {"the_branches_stacked_in_reverse"}
    result = _run(monkeypatch, _faults()[fault])
    compared = result["compared"]
    assert result["correct"] is False
    if fault in _IN_THE_SOLVE:
        caught = ("weights_gap", "scores_gap")
        # The signs and the fitted map's features are a sound fit's.
        assert compared["signs_gap"]["value"] == 0.0
        assert compared["features_gap"]["value"] < compared["features_gap"]["limit"]
    else:
        caught = ("signs_gap", "features_gap", "weights_gap")
    for name in caught:
        assert compared[name]["value"] > 1.5 * compared[name]["limit"], compared


def test_same_seed_same_inputs():
    spec = harness.load_cell(CELL, OVERRIDES)
    make = spec["adapter"].make_data
    a, b = make(7, spec["sizes"]), make(7, spec["sizes"])
    c = make(2**31 + 9, spec["sizes"])
    assert (a["x"] == b["x"]).all() and (a["y"] == b["y"]).all()
    assert not (a["x"] == c["x"]).all()
    assert a["x"].shape == (1536, 784) and a["x_held_out"].shape == (64, 784)
    assert 0.0 <= float(a["x"].min()) and float(a["x"].max()) <= 255.0


def test_the_control_reads_through_the_same_comparison(two_of_three_kept):
    """The wiring of the chip's control run (tools/control.py): the
    reference one step down, put in the program's place, against the
    reference, each on the signs it draws. On a CPU every precision is
    exact float32, so the control reads no more than float32's rounding
    (the DFT's product against NumPy's float64 FFT on the held-out rows);
    on the chip its gaps are the limits' upper readings."""
    import sys

    sys.path.insert(0, os.path.join(harness.HERE, "tools"))
    import control

    spec = harness.load_cell(CELL, OVERRIDES)
    adapter, sizes = spec["adapter"], spec["sizes"]
    data = adapter.make_data(5, sizes)
    answers = adapter.answers(adapter.fit(data, sizes), data, sizes)
    reference = adapter.reference(data, sizes, answers, "highest")
    lower = adapter.reference(data, sizes, answers, control.STEP_DOWN["highest"])
    keys = {"signs_gap", "features_gap", "weights_gap", "scores_gap"}
    assert set(control.gaps(lower, reference)) == keys
    assert set(control.gaps(answers, reference, True)) == keys
    assert reference["features"].shape == (64, 3 * 1024)  # blocks 0, 1, 2
    assert reference["scores"].shape == (64, 10)
    assert reference["weights"].shape == (3072, 10)
    assert (reference["signs"] == answers["signs"]).all()  # drawn, not taken
    assert reference["signs"].shape == (6, 784)
    assert answers["facts"] == adapter.expected_facts(sizes)


def test_canonical_work_hand_counts():
    """flops() and bytes_moved() of the configuration at its published
    sizes, against the arithmetic by hand."""
    spec = harness.load_cell(CELL)
    sizes, adapter = spec["sizes"], spec["adapter"]
    n, d, k, b, epochs = 60000, 102400, 10, 2048, 3
    assert adapter.expected_facts(sizes) == {
        "feature_dim": d, "block_size": b, "blocks": 50, "classes": k,
        "branches": 200, "per_block": 4}
    assert adapter.feature_blocks(sizes) == [0, 24, 49]
    f = adapter.flops(sizes, work)["solver"]
    grams = 50 * 2 * n * b * b
    assert grams == pytest.approx(25.17e12, rel=1e-3)  # 25.2 TFLOP of grams a fit
    ffts = 200 * n * 2.5 * 1024 * 10
    assert f == pytest.approx(work.bcd_flops(n, d, k, b, epochs) + ffts)
    moved = adapter.bytes_moved(sizes, work)["solver"]
    assert moved == pytest.approx(work.bcd_bytes(n, d, k, b, epochs)
                                  + 50 * n * (784 + 2048) * 4)
    least, bound = work.roofline_seconds(f, moved, work.chip_peaks("TPU v5 lite"))
    # Memory-bound by the count: A read once for the grams and three times
    # an epoch, 246 GB, against 25.8 TFLOP.
    assert bound == "memory" and least == pytest.approx(moved / 819e9)


def test_traced_run_holds_the_span_metric(monkeypatch, two_of_three_kept):
    """`feature_blocks_per_fit` is the span's sum a fit: 3 blocks made in
    the first epoch and the one with no slot made again in each of two
    more."""
    result = _run(monkeypatch, lambda adapter, data, sizes: adapter.fit(data, sizes),
                  trace=True, seed=4200000009)
    metrics = result["metrics"]
    assert metrics["feature_blocks_per_fit"] == {"value": 5.0, "unit": "count"}
    assert metrics["compiles_per_fit"]["value"] == 0
    assert "solver_roofline" not in metrics  # no device plane on a CPU
    assert result["fits"]["count"] == 2  # the workload's traced_fits


def test_the_metric_reads_nothing_without_the_span():
    """A program without the generated-block solve has no `bcd.generated`
    span: the reader returns None, and the result line leaves it out."""
    import spanreaders
    import spanreaders_count

    root = {"id": 1, "parent_id": None, "root_id": 1, "name": "fit",
            "start_ns": 0, "dur_ns": 10, "args": {}}
    child = {"id": 2, "parent_id": 1, "root_id": 1, "name": "solver.setup",
             "start_ns": 1, "dur_ns": 2, "args": {}}
    made = dict(child, id=3, name="bcd.generated", args={"generated_blocks": 100})
    read = []
    for ring in ([root, child], [root, made]):
        ctx = {"fits": 1}
        spanreaders.window(ctx, ring=ring)
        read.append(spanreaders_count.span_attr_sum(
            ctx, name="bcd.generated", attr="generated_blocks"))
    assert read == [None, 100]
