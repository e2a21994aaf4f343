"""``KernelRidgeRegression``: upstream's block Gauss-Seidel with every kernel
block generated inside the solver's one program, ``KernelBlockLinearMapper``
with its arrays as arguments, and the pipeline that puts them behind the
CIFAR random-patch featurizer.

Tiny widths on the CPU (the 8-device fake mesh of ``conftest.py``, so the
sharded lowering runs). The plain reference is the benchmark adapter's
(``benchmark/configs/cifar-random-patch-kernel.py``: ``solve``, the
configuration's equations in a Python loop over blocks), loaded by path as
``benchmark/tests`` load it; it imports nothing of the program.
"""

import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from keystone_tpu.config import config
from keystone_tpu.linalg import RowMatrix
from keystone_tpu.nodes.learning import (
    GaussianKernelGenerator,
    KernelBlockLinearMapper,
    KernelRidgeRegression,
    LinearKernelGenerator,
    kernel_ridge,
)
from keystone_tpu.pipelines.images import random_patch_cifar_kernel as cifar_kernel
from keystone_tpu.utils import mesh as mesh_util
from keystone_tpu.utils.metrics import (
    CompileEventCounter,
    program_counters,
    recorded_tracer,
    reset_tracer,
)

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
# One compile oracle a process (registration is permanent).
COMPILES = CompileEventCounter()


@pytest.fixture(scope="module")
def adapter():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "cifar_random_patch_kernel_adapter",
        os.path.join(BENCH, "configs", "cifar-random-patch-kernel.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _problem(seed, n, d=6, k=3):
    r = np.random.default_rng(seed)
    return (r.normal(size=(n, d)).astype(np.float32),
            r.normal(size=(n, k)).astype(np.float32))


def _gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def _arguments(text):
    """How many arguments the lowered module's entry function takes."""
    main = [line for line in text.split("\n") if "func.func public @main" in line][0]
    return len(re.findall(r"%arg\d+: tensor", main))


def _kernel(X, gamma):
    X = np.asarray(X, np.float64)
    return np.exp(-gamma * ((X[:, None, :] - X[None, :, :]) ** 2).sum(-1))


# ------------------------------------------- program against the reference


@pytest.mark.parametrize("n, block", [
    (150, 32),   # a ragged tail of 22, and rows the mesh has to pad
    (160, 40),   # blocks that tile the rows
    (90, 128),   # one block, wider than the rows
])
def test_two_epochs_match_the_plain_reference(adapter, n, block):
    X, Y = _problem(n, n)
    gamma, lam = 0.2, 0.5
    model = KernelRidgeRegression(
        GaussianKernelGenerator(gamma), lam=lam, block_size=block, num_epochs=2).fit(X, Y)
    sizes = {"rows": n, "block_size": block, "num_epochs": 2, "gamma": gamma, "lam": lam}
    with jax.default_matmul_precision("highest"):
        alpha = adapter.solve(jnp.asarray(X), jnp.asarray(Y), sizes)
        scores = adapter.scores_of(jnp.asarray(X[:17]), jnp.asarray(X), alpha, sizes)
    assert model.alpha.shape == (n, 3)
    # float32 on both sides, Cholesky of the same b x b blocks at condition
    # numbers of a few hundred: 1e-7 x 1e2, with room.
    assert _gap(model.alpha, alpha) < 1e-4
    assert _gap(model(X[:17]), scores) < 1e-4


def _each_epoch_its_own_order(num_blocks, num_epochs):
    r = np.random.default_rng(5)
    return np.concatenate([r.permutation(num_blocks) for _ in range(num_epochs)]).astype(np.int32)


def _first_epoch_a_block_short(num_blocks, num_epochs):
    visits = np.tile(np.arange(num_blocks, dtype=np.int32), num_epochs)
    visits[num_blocks - 1] = 0  # the last block's first visit comes an epoch late
    return visits


NATURAL = kernel_ridge._visit_order


@pytest.mark.parametrize("order", [
    NATURAL, _each_epoch_its_own_order, _first_epoch_a_block_short],
    ids=["natural", "permuted", "outside-the-contract"])
@pytest.mark.parametrize("keep", [0, 2, 5])
def test_the_kept_blocks_are_the_blocks_made_again(adapter, monkeypatch, keep, order):
    """Three epochs over 150 rows in blocks of 32 (a ragged tail of 22),
    none, two or all five of the first epoch's blocks kept: the dual
    weights are the program's without a store bit for bit, the span counts
    the blocks generated, and an order whose first epoch leaves a block out
    keeps nothing."""
    X, Y = _problem(31, 150)
    gamma, lam = 0.2, 0.5
    A, B = RowMatrix.from_array(X), RowMatrix.from_array(Y)
    monkeypatch.setattr(kernel_ridge, "_visit_order", order)
    monkeypatch.setattr(config, "trace", True)

    def solve(slots):
        reset_tracer()
        monkeypatch.setattr(kernel_ridge, "_blocks_kept", lambda *sizes: slots)
        alpha = np.asarray(kernel_ridge.kernel_block_gauss_seidel(
            A, B, GaussianKernelGenerator(gamma), lam, block_size=32, num_epochs=3))
        return alpha, recorded_tracer().spans()[-1]["args"]

    plain, _ = solve(0)
    alpha, span = solve(keep)
    assert np.array_equal(alpha, plain)
    kept = 0 if order is _first_epoch_a_block_short else keep
    assert {k: span[k] for k in (
        "blocks", "epochs", "kernel_blocks", "kernel_bytes", "blocks_kept", "store_bytes")} == {
        "blocks": 5, "epochs": 3, "kernel_blocks": 15 - 2 * kept,
        "kernel_bytes": (15 - 2 * kept) * 160 * 32 * 4,
        "blocks_kept": kept, "store_bytes": kept * 160 * 32 * 4}
    reset_tracer()
    if order is NATURAL:
        sizes = {"rows": 150, "block_size": 32, "num_epochs": 3, "gamma": gamma, "lam": lam}
        with jax.default_matmul_precision("highest"):
            want = adapter.solve(jnp.asarray(X), jnp.asarray(Y), sizes)
        assert _gap(alpha[:150], want) < 1e-4


# (one shard's rows, block, blocks, epochs, the shard's X and Y in bytes,
# the device's limit) -> slots, float32. 16,909,336,064 is what a v5e
# reports (PERF.md section 5).
V5E = 16_909_336_064


@pytest.mark.parametrize("rows, block, blocks, epochs, limit, keep", [
    (50_000, 4096, 13, 3, V5E, 13),        # cifar-kernel-fit: room for 14, all 13 kept
    (50_000, 4096, 13, 1, V5E, 0),         # one epoch visits nothing twice
    (50_000, 4096, 13, 2, V5E, 13),
    (100_000, 4096, 25, 3, V5E, 5),        # twice the rows: slots of 1.64 GB
    (500_000, 4096, 123, 3, V5E, 0),       # the augmented set: a slot is 8.2 GB
    (125_000, 4096, 123, 3, V5E, 3),       # the same on a four-wide mesh: 12 slots in all
    (12_512, 4096, 13, 3, V5E, 13),        # cifar-kernel-fit's rows a shard of four
    (50_000, 4096, 13, 3, 12 * 2**30, 9),  # the CPU's config.hbm_budget_bytes
    (50_000, 4096, 13, 3, 4 * 2**30, 0),   # no room beside the working blocks
    (160, 32, 5, 3, 12 * 2**30, 5),        # a test's sizes
])
def test_the_rule_keeps_what_fits_beside_its_reserve(rows, block, blocks, epochs, limit, keep):
    arguments = rows * (4096 + 10) * 4
    assert kernel_ridge._blocks_kept(rows, block, blocks, epochs, arguments, 4, limit) == keep


def test_the_rule_reads_the_devices_limit_or_the_cpus_budget(monkeypatch):
    """``kernel_block_gauss_seidel`` hands the rule one shard's rows and
    ``device_hbm_bytes()``, which on the CPU is ``config.hbm_budget_bytes``."""
    seen = []
    monkeypatch.setattr(kernel_ridge, "_blocks_kept", lambda *sizes: seen.append(sizes) or 0)
    monkeypatch.setattr(config, "hbm_budget_bytes", 123_456_789)
    X, Y = _problem(33, 150)
    kernel_ridge.kernel_block_gauss_seidel(
        RowMatrix.from_array(X), RowMatrix.from_array(Y), GaussianKernelGenerator(0.2),
        0.5, block_size=32, num_epochs=3)
    assert seen == [(20, 32, 5, 3, 20 * (6 + 3) * 4, 4, 123_456_789)]


def test_many_epochs_reach_the_direct_solve():
    X, Y = _problem(3, 120)
    gamma, lam = 0.3, 0.5
    model = KernelRidgeRegression(
        GaussianKernelGenerator(gamma), lam=lam, block_size=32, num_epochs=60).fit(X, Y)
    K = _kernel(X, gamma)
    direct = np.linalg.solve(K + lam * np.eye(len(X)), Y.astype(np.float64))
    assert _gap(model.alpha, direct) < 1e-4
    assert _gap(model(X), K @ direct) < 1e-4
    # One sweep from zero is not there yet: the epochs do the work.
    once = KernelRidgeRegression(
        GaussianKernelGenerator(gamma), lam=lam, block_size=32, num_epochs=1).fit(X, Y)
    assert _gap(once.alpha, direct) > 1e-2


def test_any_kernel_generator_runs_the_block_solver():
    X, Y = _problem(5, 70, d=4)
    model = KernelRidgeRegression(
        LinearKernelGenerator(), lam=2.0, block_size=16, num_epochs=80).fit(X, Y)
    K = X.astype(np.float64) @ X.astype(np.float64).T
    direct = np.linalg.solve(K + 2.0 * np.eye(len(X)), Y.astype(np.float64))
    # K has rank 4: alpha is pinned by the ridge alone in most directions,
    # so hold the scores, which the rank does not blur.
    assert _gap(model(X), K @ direct) < 1e-3


@pytest.mark.parametrize("keep", [0, 2, 5])
def test_the_fit_on_the_mesh_matches_one_device(monkeypatch, keep):
    monkeypatch.setattr(kernel_ridge, "_blocks_kept", lambda *sizes: keep)
    X, Y = _problem(7, 150)
    est = KernelRidgeRegression(
        GaussianKernelGenerator(0.2), lam=0.5, block_size=32, num_epochs=3)
    assert mesh_util.default_mesh().shape[config.data_axis] == 8
    sharded = est.fit(X, Y)
    mesh_util.set_default_mesh(Mesh(np.asarray(jax.devices()[:1]), (config.data_axis,)))
    single = est.fit(X, Y)
    # The same visits on the same blocks, kept or made again; K_B^T alpha
    # sums in the fold's order on both widths, X_B and K_BB reach every
    # shard by a psum of one non-zero term a row.
    assert _gap(sharded.alpha, single.alpha) < 1e-6
    assert _gap(sharded(X[:9]), single(X[:9])) < 1e-6


def test_pad_rows_and_the_ragged_blocks_pad_stay_zero():
    X, Y = _problem(9, 150)
    A, B = RowMatrix.from_array(X), RowMatrix.from_array(Y)
    assert A.padded_rows == 160
    alpha = kernel_ridge.kernel_block_gauss_seidel(
        A, B, GaussianKernelGenerator(0.2), lam=0.0 + 1e-3, block_size=64, num_epochs=2)
    assert alpha.shape == (160, 3)
    assert np.isfinite(np.asarray(alpha)).all()
    assert (np.asarray(alpha[150:]) == 0.0).all()
    assert np.abs(np.asarray(alpha[:150])).min() > 0.0


# ----------------------------------------------- programs found by structure


def test_a_second_fit_compiles_nothing():
    def fit(seed, gamma, lam):
        X, Y = _problem(seed, 150)
        model = KernelRidgeRegression(
            GaussianKernelGenerator(gamma), lam=lam, block_size=32, num_epochs=2).fit(X, Y)
        return model, np.asarray(model(X[:16]))

    first, _ = fit(11, 0.2, 0.5)
    before, calls = COMPILES.count, program_counters.calls()
    second, _ = fit(12, 0.35, 1.5)  # other rows, another kernel width, another ridge
    assert COMPILES.count == before
    assert program_counters.since(calls)["closure_program_calls"] == 0
    assert _gap(first.alpha, second.alpha) > 0.1  # and not the first fit's answer


@pytest.mark.parametrize("keep", [0, 2, 5])
def test_the_solvers_program_takes_its_operands_as_arguments(keep):
    mesh = mesh_util.default_mesh()
    rows, d, k, block = 160, 6, 3, 32
    f32, shape = jnp.float32, jax.ShapeDtypeStruct
    solve = kernel_ridge._block_solve_fn(
        mesh, config.data_axis, kernel_ridge._precision(),
        mesh_util.fold_blocks(mesh.shape[config.data_axis]), block, keep, 5)
    text = solve.lower(
        shape((rows, d), f32), shape((rows, k), f32), shape((), f32),
        shape((), jnp.int32), shape((10,), jnp.int32),
        GaussianKernelGenerator(0.2)).as_text()
    # X, Y, lam, n, the visits' starts and gamma: six arguments.
    assert _arguments(text) == 6
    # The loop over the visits is in the program (two with a store: the
    # epoch that fills it and the ones that read it), and no kernel larger
    # than the store's slots: nothing of rows x rows.
    assert text.count("stablehlo.while") == (2 if keep else 1)
    assert f"tensor<{rows}x{rows}x" not in text
    assert (f"tensor<{keep}x{rows // mesh.shape[config.data_axis]}x{block}x" in text) == bool(keep)


def test_the_mapper_names_its_arrays_and_applies_in_blocks():
    X, _ = _problem(13, 75)
    alpha = np.random.default_rng(14).normal(size=(75, 3)).astype(np.float32)
    mapper = KernelBlockLinearMapper(GaussianKernelGenerator(0.25), X, alpha, block_size=32)
    assert mapper.shares_program()
    queries = _problem(15, 10)[0]
    text = mapper._jitted().lower(queries).as_text()
    assert "module @jit_apply_KernelBlockLinearMapper " in text
    # gamma, X_train, alpha and the queries: four arguments, no constant
    # beyond a scalar, and the blocks walked by a loop in the program.
    assert _arguments(text) == 4
    sizes = [int(np.prod([int(n) for n in s.split("x")[:-1]] or [1]))
             for s in re.findall(r"stablehlo\.constant[^\n]*?: tensor<([^>]*)>", text)]
    assert all(n <= 32 for n in sizes), sizes
    assert "stablehlo.while" in text
    # 75 rows in blocks of 32: the last block overlaps the one before it
    # and counts its own 11 rows alone.
    want = _kernel(np.concatenate([queries, X]), 0.25)[:10, 10:] @ alpha.astype(np.float64)
    assert _gap(mapper(queries), want) < 1e-5
    # Another model of the same shapes runs the same executable.
    before = COMPILES.count
    other = KernelBlockLinearMapper(GaussianKernelGenerator(0.5), X[::-1].copy(), alpha, 32)
    np.asarray(other(queries))
    assert COMPILES.count == before


# ------------------------------------------------------------ the pipeline

CONF = cifar_kernel.RandomPatchCifarKernelConfig(
    num_filters=16, patch_size=6, patch_sample=500, patch_norm=10.0,
    pool_size=4, pool_stride=4, alpha=0.25, gamma=4e-3, lam=1.0,
    block_size=48, num_epochs=3, num_classes=5, seed=3)


def _images(seed, n=112, side=13, classes=5):
    r = np.random.default_rng(seed)
    y = r.integers(0, classes, size=n)
    u = np.arange(side)[None, :, None, None] * (0.4 + 0.3 * y[:, None, None, None])
    v = np.arange(side)[None, None, :, None] * (0.9 - 0.1 * y[:, None, None, None])
    x = 127.5 + 60 * np.sin(u + v + r.uniform(0, 6, size=(n, 1, 1, 3)))
    x = x + 25 * r.normal(size=(n, side, side, 3))
    return np.clip(x, 0, 255).astype(np.float32), y.astype(np.int32)


def test_the_pipeline_fits_and_predicts(monkeypatch):
    monkeypatch.setattr(config, "trace", True)
    reset_tracer()
    x, y = _images(21)
    fitted = cifar_kernel.fit(CONF, x, y)
    names = [type(s).__name__ for t in fitted.transformers()
             for s in getattr(t, "stages", [t])]
    assert names == ["Convolver", "SymmetricRectifier", "Pooler", "ImageVectorizer",
                     "StandardScalerModel", "KernelBlockLinearMapper", "MaxClassifier"]
    predicted = np.asarray(fitted(x).get())
    assert (predicted == y).mean() > 0.9
    held, held_y = _images(22, n=40)
    # Fresh rows: well over the one in five of a guess.
    assert (np.asarray(fitted(held).get()) == held_y).mean() > 0.4

    # Applied by itself the map has a span of its own (inside a fused
    # chain the chain's node span covers it).
    mapper = [s for t in fitted.transformers() for s in getattr(t, "stages", [t])][5]
    mapper(np.asarray(mapper.X_train[:7]))
    spans = recorded_tracer().spans()
    root = [s for s in spans if s["name"] == "fit" and s.get("parent_id") is None][-1]
    assert root["args"]["pipeline"] == "cifar-kernel"
    assert root["args"]["closure_program_calls"] == 0
    solve = [s for s in spans if s["name"] == "krr.fit"][-1]
    assert solve["root_id"] == root["id"]
    # 112 rows of 2 x 2 x 2 x 16 features in blocks of 48: 3 blocks, 3 epochs.
    assert {k: solve["args"][k] for k in (
        "rows", "dim", "block", "blocks", "epochs", "kernel_blocks", "kernel_bytes",
        "blocks_kept", "store_bytes")} == {
        "rows": 112, "dim": 128, "block": 48, "blocks": 3, "epochs": 3,
        # The CPU's budget holds all three: one generation each, two reads.
        "kernel_blocks": 3, "kernel_bytes": 3 * 112 * 48 * 4,
        "blocks_kept": 3, "store_bytes": 3 * 112 * 48 * 4}
    applied = [s for s in spans if s["name"] == "krr.apply"]
    assert applied[-1]["args"] == {"rows": 7, "train_rows": 112, "blocks": 3}
    reset_tracer()


def test_the_cli_runs_the_pipeline(capsys):
    out = cifar_kernel.main([
        "--synthetic-n", "256", "--num-filters", "16", "--patch-sample", "500",
        "--block-size", "100", "--num-epochs", "2"])
    assert out["test_accuracy"] > 0.8
    assert "total accuracy" in capsys.readouterr().out
