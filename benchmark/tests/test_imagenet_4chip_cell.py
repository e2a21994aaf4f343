"""imagenet-fit-4chip at tiny widths on the CPU: a whole run through the
harness on a fake mesh of four, the deployment's own planted fault (a
shard's rows left out of one reduction), and the collectives' readers on
op tables with and without a collective. Counts and `correct` only.

The mesh needs four devices, which the CPU backend has only if
``--xla_force_host_platform_device_count`` was in ``XLA_FLAGS`` before it
started: where it was not, ``test_on_a_fake_mesh_of_four`` runs this file
again in a process of its own that has it, and the mesh's tests skip here.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "imagenet-fit-4chip"
FLAG = "--xla_force_host_platform_device_count=4"

with open(os.path.join(HERE, "data", "tiny-imagenet-4chip.json")) as f:
    TINY = json.load(f)


@pytest.fixture
def mesh_of_four():
    import jax

    if jax.device_count() < 4:
        pytest.skip("fewer than four devices: test_on_a_fake_mesh_of_four runs these")
    from keystone_tpu.utils.mesh import (
        default_mesh,
        reset_default_mesh,
        set_default_mesh,
    )
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.reset()
    set_default_mesh(default_mesh(devices=jax.devices()[:4]))
    yield
    reset_default_mesh()
    PipelineEnv.reset()


def test_on_a_fake_mesh_of_four():
    import jax

    if jax.device_count() >= 4:
        return  # the mesh's tests below run in this process
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " " + FLAG).strip())
    out = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.abspath(__file__), "-q", "-p",
         "no:cacheprovider", "-k", "mesh"],
        capture_output=True, text=True, env=env, cwd=harness.ROOT)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    assert " skipped" not in out.stdout.splitlines()[-1], out.stdout[-500:]


def _run(monkeypatch=None, plant=None):
    adapter = harness.load_cell(CELL)["adapter"]
    if plant is not None:
        class Broken:
            """The adapter with its fit broken; the rest is the adapter's."""

            def __getattr__(self, name):
                return getattr(adapter, name)

            def fit(self, data, sizes):
                return plant(adapter, data, sizes)

        monkeypatch.setattr(harness, "load_adapter", lambda file_name: Broken())
    return harness.run_cell(CELL, 2200000021, 0.2, False, need_tpu=False, overrides=TINY)


def test_a_run_on_the_mesh_is_correct_and_well_formed(mesh_of_four):
    line = json.loads(json.dumps(_run()))  # what run.py prints
    assert line["correct"] is True, line["compared"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"fit_s", "setup_s"}
    assert line["device"]["count"] >= 4 and line["fits"]["window_compiles"] == 0
    assert line["compared"]["widths_off"] == {"value": 0, "limit": 0}
    spec = harness.load_cell(CELL, TINY)
    assert spec["chips"] == 4
    facts = spec["adapter"].expected_facts(spec["sizes"])
    assert facts["shards"] == 4 and facts["rows_per_shard"] == 24


def _faults():
    path = os.path.join(harness.HERE, "configs", "imagenet-sift-lcs-fv-64k-x4-control.py")
    spec = importlib.util.spec_from_file_location("x4_control", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.FAULTS


@pytest.mark.parametrize("fault,caught_by", [
    ("a_shard_left_out_of_the_grams", "scores_gap"),
    ("a_shard_left_out_of_the_em_mass", "sift_density_gap"),
    ("a_block_left_unsolved", "scores_gap"),
])
def test_a_fault_planted_on_the_mesh_is_not_correct(mesh_of_four, monkeypatch, fault, caught_by):
    """One shard's rows left out of one reduction across the mesh (every
    gram, or every EM sweep's mass, summed over three shards of four), and
    a weight block zeroed: `correct` comes out false, by the number named."""
    result = _run(monkeypatch, _faults()[fault])
    assert result["correct"] is False
    check = result["compared"][caught_by]
    assert check["value"] > check["limit"], result["compared"]


def test_the_sound_fit_passes_the_same_way_on_the_mesh(mesh_of_four, monkeypatch):
    result = _run(monkeypatch, lambda adapter, data, sizes: adapter.fit(data, sizes))
    assert result["correct"] is True, result["compared"]


# ------------------------------------------------------------- the readers

OPS_ONE_CHIP = [
    ["jit_local", "fusion.170", 0.40, ["Cholesky"]],
    ["jit__fit_gmm", "fusion.116", 0.12, []],
    ["jit_local", "all-reduce-scatter-fusion.3", 0.05, []],  # a fusion, not a collective
]
OPS_MESH = OPS_ONE_CHIP + [
    ["jit_local", "collective-permute-start.4", 0.010, ["Cholesky"]],
    ["jit_local", "collective-permute-done.4", 0.070, ["Cholesky"]],
    ["jit_local", "all-reduce.7", 0.020, []],
    ["jit__fit_gmm", "collective-permute.1", 0.004, []],
]


def _ctx(ops, **more):
    return dict({"trace": {"ops": ops}, "fits": 2, "chips": 4, "notes": []}, **more)


def test_the_readers_find_the_collectives_by_name():
    assert harness.read_metric("collective_exposed", _ctx(OPS_MESH)) == pytest.approx(52.0)
    least = 0.013
    share = harness.read_metric(
        "collective_roofline", _ctx(OPS_MESH, bytes={"solver": 1.0, "collective_least_s": least}))
    assert share == pytest.approx(100.0 * least / 0.052)


def test_the_readers_return_none_and_never_nought():
    # One chip: no collective in the table; no table at all; no ICI rate.
    assert harness.read_metric("collective_exposed", _ctx(OPS_ONE_CHIP)) is None
    assert harness.read_metric("collective_exposed", _ctx([])) is None
    assert harness.read_metric("collective_exposed", {"trace": None, "fits": 2}) is None
    assert harness.read_metric(
        "collective_roofline", _ctx(OPS_ONE_CHIP, bytes={"collective_least_s": 0.01})) is None
    assert harness.read_metric("collective_roofline", _ctx(OPS_MESH, bytes={"solver": 1.0})) is None


def _roots(*collective_bytes):
    return [{"name": "fit", "id": i + 1, "root_id": i + 1, "parent_id": None,
             "start_ns": 1000 * i, "dur_ns": 900,
             "args": {"rows": 96} if b is None else {"rows": 96, "collective_bytes": b}}
            for i, b in enumerate(collective_bytes)]


def test_collective_gib_reads_the_roots_counter():
    import spanreaders

    ctx = {"fits": 2}
    spanreaders.window(ctx, ring=_roots(3 * 2**30, 3 * 2**30))
    assert harness.read_metric("collective_gib", ctx) == pytest.approx(3.0)
    # A program whose root counts none (the parent), and one device's nought.
    for ring in (_roots(None, None), _roots(0, 0)):
        ctx = {"fits": 2}
        spanreaders.window(ctx, ring=ring)
        assert harness.read_metric("collective_gib", ctx) is None


def test_the_adapter_counts_the_collectives_from_the_sizes():
    spec = harness.load_cell(CELL)
    adapter, sizes = spec["adapter"], spec["sizes"]
    total = adapter.collective_bytes(sizes)
    grams, atr = 8 * 8192 * 8192 * 4, 3 * 8 * 8192 * 1000 * 4
    samples = 200000 * (128 + 96) * 4
    assert grams + atr + samples < total < grams + atr + samples + 8e6
    assert adapter.expected_facts(sizes)["rows_per_shard"] * sizes["shards"] == sizes["rows"]
    assert sizes["rows"] == 32768 and sizes["rows_per_shard"] == 8192
    one_chip = harness.load_cell("imagenet-fit")["sizes"]
    assert {k: v for k, v in sizes.items()
            if k not in ("rows", "shards", "rows_per_shard", "hosts")} == \
        {k: v for k, v in one_chip.items() if k != "rows"}
