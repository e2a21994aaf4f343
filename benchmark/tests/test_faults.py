"""The rest of a run with the timed path broken underneath: `correct` has
to come out false, once for each fault a fit cell can have (`FAULTS` of
tools/control.py, which reads the same faults on the chip at full width).
One chip has no exchange to leave out. The step that returns its state all
but unchanged, a mixture given one EM sweep of twenty, shows only at the
cell's own size (4 components on 2,000 descriptors differ more from fit to
fit than from sweep to sweep): `tests/test_control.py` holds it, on a chip."""

import os
import sys

import numpy as np
import pytest

import harness
from conftest import TINY, TINY_LIMITS

sys.path.insert(0, os.path.join(harness.HERE, "tools"))
import control


def _run(cell, monkeypatch, plant=None):
    adapter = harness.load_cell(cell)["adapter"]
    if plant is not None:
        class Broken:
            """The adapter with its fit broken; the rest is the adapter's."""

            def __getattr__(self, name):
                return getattr(adapter, name)

            def fit(self, data, sizes):
                return plant(adapter, data, sizes)

        monkeypatch.setattr(harness, "load_adapter", lambda file_name: Broken())
    return harness.run_cell(
        cell, 2200000003, 0.2, False, need_tpu=False,
        overrides={"sizes": TINY[cell], "limits": TINY_LIMITS})


@pytest.mark.parametrize("fault", ["half_the_batch", "a_block_left_unsolved"])
@pytest.mark.parametrize("cell", sorted(TINY))
def test_a_planted_fault_is_not_correct(cell, fault, monkeypatch):
    result = _run(cell, monkeypatch, control.FAULTS[fault])
    assert result["correct"] is False
    assert result["compared"]["scores_gap"]["value"] > TINY_LIMITS["scores_gap"]


def test_a_pca_from_a_hundredth_of_the_sample_is_not_correct():
    """The reference's own fit, broken and put in the program's place."""
    spec = harness.load_cell("imagenet-fit", {"sizes": TINY["imagenet-fit"]})
    adapter, sizes = spec["adapter"], spec["sizes"]
    data = adapter.make_data(2200000005, sizes)
    answers = adapter.answers(adapter.fit(data, sizes), data, sizes)
    sound = adapter.reference(data, sizes, answers, solve=False)
    broken = adapter.reference(data, sizes, answers, solve=False,
                               fault=control.REFERENCE_FAULTS["ref_a_hundredth_of_the_sample"])
    limits = {k: v for k, v in TINY_LIMITS.items() if "pca_residual" in k}
    checks = harness.compare(broken, sound, limits)
    assert all(c["value"] > c["limit"] for c in checks.values()), checks
    kept = harness.compare(dict(answers, **sound["measured"]), sound, limits)
    assert all(c["value"] <= c["limit"] for c in kept.values()), kept


@pytest.mark.parametrize("cell", sorted(TINY))
def test_the_sound_fit_passes_the_same_way(cell, monkeypatch):
    result = _run(cell, monkeypatch, lambda adapter, data, sizes: adapter.fit(data, sizes))
    assert result["correct"] is True


def test_a_non_finite_answer_is_not_correct():
    checks = harness.compare({"scores": np.array([1.0, np.nan])},
                             {"scores": np.array([1.0, 2.0])}, {"scores_gap": 1.0})
    assert checks["scores_gap"]["value"] == float("inf")
