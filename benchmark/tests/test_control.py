"""The control has to come out as not correct: the reference, put in the
program's place and computed one precision step below the configuration's
(`high`, three bf16 passes, for float32 at `highest`), fails one of the
cell's limits, where the timed path keeps them all. So has the fit with its
mixture given one EM sweep of twenty, which tiny widths cannot show
(`test_faults.py`).

Precision is the chip's: the CPU ignores it, so this runs where a TPU is
(`chiprun -- python3 -m pytest benchmark/tests/test_control.py -q`), at the
cell's own size, which one chip holds in a few minutes. The readings of
PERF.md section 2 came from `tools/control.py`, the same code."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "tools"))


@pytest.fixture(scope="module")
def tpu():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("matmul precision is a TPU's: the CPU computes float32 whatever is asked")


@pytest.mark.parametrize("cell", ["imagenet-fit"])
def test_control_and_one_em_sweep_are_not_correct(tpu, cell, monkeypatch):
    import control
    import harness

    monkeypatch.setattr(control, "REFERENCE_FAULTS", {})
    monkeypatch.setattr(control, "FAULTS", {"one_em_sweep": control.one_em_sweep})
    spec, _device, _ = harness.prepare(cell)
    out = control.readings(spec, 2200000011, True, True, lambda line: None)
    limits = spec["limits"]
    assert out["widths_off"] == 0
    assert all(out["program"][k] <= limits[k] for k in limits), out
    assert any(out["control"][k] > limits[k] for k in limits), out
    assert any(out["faults"]["one_em_sweep"][k] > limits[k] for k in limits), out
