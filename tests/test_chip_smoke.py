"""chip_smoke.py and the platform helpers it stands on, as far as a CPU can
show: where the compile cache goes, that a path which needs a TPU says so,
and that the smoke's four phases run end to end (here at tiny widths on the
8-device CPU mesh; the published widths run on the chip)."""

import json
import os
import subprocess
import sys

import jax
import pytest

from keystone_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_updates(monkeypatch):
    """Every value ``setup_compile_cache`` hands to ``jax.config.update``,
    recorded instead of applied (the suite keeps its own setting)."""
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda _name, value: updates.append(value)
    )
    return updates


def test_compile_cache_from_env_is_left_alone(monkeypatch, tmp_path, cache_updates):
    ours = os.path.join(REPO, ".xla_compile_cache")
    existed = os.path.exists(ours)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.setup_compile_cache() == str(tmp_path)
    assert cache_updates == []  # JAX read the variable itself
    assert os.path.exists(ours) == existed


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(
    monkeypatch, cache_updates
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ours = os.path.join(REPO, ".xla_compile_cache")
    assert platform.setup_compile_cache() == ours
    assert platform.setup_compile_cache() == ours  # same key every time
    assert cache_updates == [ours, ours]


def test_needing_a_tpu_raises_on_cpu():
    info = platform.device_info()
    assert info == {"platform": "cpu", "kind": "cpu", "count": 8}
    with pytest.raises(RuntimeError, match="no TPU"):
        platform.device_info(need_tpu=True)


def test_smoke_phases_at_tiny_widths(monkeypatch):
    sys.path.insert(0, REPO)
    import chip_smoke

    from keystone_tpu.config import config

    # 7 rungs on 2 replicas, not the default 11 on 8: the CPU compiles each.
    monkeypatch.setattr(config, "serve_max_batch", 64)
    monkeypatch.setattr(config, "serve_devices", 2)
    conf = chip_smoke.SmokeConfig(
        pca_dims=8, gmm_k=4, classes=8, epochs=2, images=128, kernel_m=64,
        conv_filters=16, conv_rows=5,
    )
    report = chip_smoke.smoke(conf)
    assert report["widths"]["feature_dim"] == 2 * (2 * 4 * 8)
    assert report["operand_devices"] == 8
    serving = report["serving"]
    assert serving["replicas"] == 2 and serving["post_warmup_compiles"] == 0
    assert serving["warmup_compiles"] == 2 * len(serving["ladder"])
    assert all(serving["replica_dispatches"].values())
    assert serving["requests"] == 6  # (1, 3, 37) rows over each wire
    # On the CPU the kernel runs in the Pallas interpreter, and says so.
    counters = report["sharding_counters"]
    # (the Fisher-vector kernel three times, the convolver's once)
    assert counters["pallas_interpret_calls"] == 4
    assert report["kernel"]["rel_err_vs_xla"]["conv_rectify_pool"] < 1e-5
    assert "pallas_mosaic_calls" not in counters
    assert set(report["numerics"]["rel_err_vs_cpu"]) == {"features", "scores"}
    # What main() prints last: the report, then a verdict of exactly "ok"
    # and the device. Whoever runs the script parses that last line.
    lines = chip_smoke.result_lines(platform.device_info(), conf, report)
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }
    assert json.loads(lines[-2])["widths"] == report["widths"]
    assert all("\n" not in line for line in lines)


def test_chip_smoke_script_fails_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=platform.cpu_mesh_env(1), capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line
