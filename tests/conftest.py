"""Test fixture: a virtual 8-device CPU mesh.

The reference tests all "distributed" logic on a local[n] SparkContext
(Ref: src/test/scala shared LocalSparkContext trait [unverified]); our analog
is XLA's forced host-platform device count — the same collective code paths
run on 8 fake CPU devices as on a TPU pod slice.
"""

import os

# XLA_FLAGS must be in the env before the CPU backend initializes.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

assert len(jax.devices()) == 8, f"expected 8 CPU devices, got {jax.devices()}"


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def fresh_env():
    """Fresh PipelineEnv per test — the analog of a fresh SparkContext.
    The default-mesh memo resets too, so a test that installed a narrow
    mesh via ``set_default_mesh`` (fake device counts) can never leak a
    memoized 1-device mesh into a later 8-device test."""
    from keystone_tpu.utils.mesh import reset_default_mesh
    from keystone_tpu.workflow.executor import PipelineEnv

    PipelineEnv.reset()
    reset_default_mesh()
    yield
    PipelineEnv.reset()
    reset_default_mesh()


def assert_same_to_rounding(got, want, limit: float = 1e-5):
    """``got`` is ``want`` up to the order in which float32 sums were made:
    the norm of the difference is at most ``limit`` of ``want``'s norm.

    What a mesh computes and what one device computes were pinned byte for
    byte until the installed XLA:CPU (jax 0.9.0) began to choose its dot
    kernel by shape: a product of 9 rows a shard and one of all 72 rows now
    sum each inner product in another order (1.2e-6 apart in a third of the
    entries; the commit the tests came with fails the same way here). A
    changed order moves a float32 inner product by a few ulp times the root
    of its depth, 1e-6 of the norm at these widths, and a solve on top of it
    by its condition number times that; a wrong row, a pad row that leaked
    or a shard left out moves it by 1e-2 or more."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    gap = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert gap <= limit, f"{gap:.3g} of the norm apart (limit {limit:g})"
