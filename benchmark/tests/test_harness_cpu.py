"""A whole run of each cell through the harness's functions, at tiny
widths on the CPU: counts and `correct` only, never a speed."""

import json

import pytest

import harness
from conftest import TINY, TINY_LIMITS


@pytest.mark.parametrize("cell", sorted(TINY))
def test_run_is_correct_and_well_formed(cell):
    result = harness.run_cell(
        cell, 2200000001, 0.5, False, need_tpu=False,
        overrides={"sizes": TINY[cell], "limits": TINY_LIMITS})
    line = json.loads(json.dumps(result))  # what run.py prints
    assert line["correct"] is True, line["compared"]
    assert list(line)[-1] == "compared"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"fit_s", "setup_s"}
    assert line["metrics"]["fit_s"]["value"] > 0
    assert line["fits"]["ends_s"][-1] >= 0.5  # the window ran its length
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]


def test_same_seed_same_inputs():
    spec = harness.load_cell("imagenet-fit", {"sizes": TINY["imagenet-fit"]})
    a = spec["adapter"].make_data(7, spec["sizes"])
    b = spec["adapter"].make_data(7, spec["sizes"])
    c = spec["adapter"].make_data(2**31 + 7, spec["sizes"])
    assert (a["x"] == b["x"]).all() and (a["y"] == b["y"]).all()
    assert not (a["x"] == c["x"]).all()


def test_run_py_refuses_a_cpu():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload", "imagenet-fit",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT)
    assert out.returncode != 0 and out.stdout.strip() == ""
