"""Pallas kernel tests (interpreter mode on the CPU mesh; same code lowers
through Mosaic on TPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.nodes.images.external.fisher_vector import FisherVector, _fv_tpu
from keystone_tpu.ops import fisher_vectors_pallas


@pytest.fixture
def gmm(rng):
    k, d = 4, 8
    w = rng.uniform(0.1, 1.0, size=k).astype(np.float32)
    w /= w.sum()
    mu = rng.normal(size=(k, d)).astype(np.float32)
    var = rng.uniform(0.5, 1.5, size=(k, d)).astype(np.float32)
    return w, mu, var


def test_pallas_fv_matches_xla(rng, gmm):
    w, mu, var = gmm
    X = rng.normal(size=(3, 100, 8)).astype(np.float32)
    out_p = np.asarray(fisher_vectors_pallas(X, w, mu, var, tile_m=32))
    out_x = np.asarray(
        _fv_tpu(jnp.asarray(X), jnp.asarray(w), jnp.asarray(mu), jnp.asarray(var))
    )
    # 100 % 32 != 0: the padded-tile mask path is exercised.
    np.testing.assert_allclose(out_p, out_x, rtol=1e-4, atol=1e-5)


def test_pallas_fv_tile_size_invariance(rng, gmm):
    w, mu, var = gmm
    X = rng.normal(size=(2, 64, 8)).astype(np.float32)
    a = np.asarray(fisher_vectors_pallas(X, w, mu, var, tile_m=16))
    b = np.asarray(fisher_vectors_pallas(X, w, mu, var, tile_m=64))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_fisher_vector_node_pallas_backend(rng, gmm):
    w, mu, var = gmm
    X = rng.normal(size=(2, 50, 8)).astype(np.float32)
    node_p = FisherVector(w, mu, var, backend="pallas")
    node_t = FisherVector(w, mu, var, backend="tpu")
    np.testing.assert_allclose(
        np.asarray(node_p(X)), np.asarray(node_t(X)), rtol=1e-4, atol=1e-5
    )
    assert node_p.jittable


def test_pallas_fv_zero_weight_component(rng):
    # A starved component must produce a zero block, not NaNs (same clamp
    # as the other backends).
    k, d = 3, 4
    w = np.array([0.5, 0.5, 0.0], dtype=np.float32)
    mu = rng.normal(size=(k, d)).astype(np.float32)
    var = np.ones((k, d), dtype=np.float32)
    X = rng.normal(size=(1, 40, d)).astype(np.float32)
    out = np.asarray(fisher_vectors_pallas(X, w, mu, var))
    assert np.isfinite(out).all()


# ------------------------- convolution, rectifier and pooling as one kernel


def _conv_triple(rng, filters, pool, mode, bias, normalise, max_val):
    from keystone_tpu.nodes.images import Convolver, Pooler, SymmetricRectifier
    from keystone_tpu.nodes.learning.zca import ZCAWhitener

    f = rng.normal(size=(filters, 6, 6, 3)).astype(np.float32)
    whitener = None
    if bias:  # a folded whitener is where a convolver's bias comes from
        S = rng.normal(size=(108, 108))
        whitener = ZCAWhitener(
            (S @ S.T / 108 + np.eye(108)).astype(np.float32),
            (rng.normal(size=108) * 0.1).astype(np.float32))
    conv = Convolver(f, whitener=whitener,
                     normalize_patches=10.0 if normalise else None)
    assert (conv.bias is not None) == bias
    size, stride = pool
    return conv, SymmetricRectifier(alpha=0.25, max_val=max_val), Pooler(
        stride, size, mode=mode)


@pytest.mark.parametrize(
    "side, pool, mode, bias, normalise, filters, rows, max_val", [
        # 14 / 13 on 27 positions: position 13 belongs to two windows.
        (32, (14, 13), "sum", True, True, 16, 5, 0.0),
        (32, (14, 13), "mean", False, True, 130, 1, 0.0),
        (32, (14, 13), "sum", True, False, 32, 37, 0.05),
        # 4 / 4 on 8: disjoint windows.
        (13, (4, 4), "sum", True, True, 16, 37, 0.0),
        (13, (4, 4), "mean", True, True, 32, 5, 0.1),
        (13, (4, 4), "sum", False, False, 130, 1, 0.0),
        (13, (4, 4), "sum", False, True, 32, 1, 0.0),
        (13, (4, 4), "mean", True, False, 130, 37, 0.0),
        # Windows that leave positions out (3 / 3 on 8) and that overlap
        # by more than they leave (3 / 2 on 9: most positions in four).
        (13, (3, 3), "sum", True, True, 16, 5, 0.0),
        (14, (3, 2), "sum", True, True, 16, 5, 0.0),
    ])
def test_conv_rectify_pool_matches_the_stage_walk(
        rng, side, pool, mode, bias, normalise, filters, rows, max_val):
    from keystone_tpu.utils.metrics import sharding_counters

    conv, rectifier, pooler = _conv_triple(
        rng, filters, pool, mode, bias, normalise, max_val)
    x = jnp.asarray(
        rng.uniform(0, 255, size=(rows, side, side, 3)).astype(np.float32))
    if not normalise:
        x = x / 255.0
    want = np.asarray(pooler.apply_batch(rectifier.apply_batch(
        conv.apply_batch(x))))
    assert conv.takes([rectifier, pooler]) == 2
    before = sharding_counters.snapshot().get("pallas_interpret_calls", 0)
    got = np.asarray(conv.apply_with([rectifier, pooler], x))
    counters = sharding_counters.snapshot()
    assert counters["pallas_interpret_calls"] == before + 1
    assert got.shape == want.shape and got.shape[-1] == 2 * filters
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_conv_rectify_pool_in_bfloat16_is_the_convolvers_throughput_mode(rng):
    conv, rectifier, pooler = _conv_triple(
        rng, 32, (4, 4), "sum", True, True, 0.0)
    conv.compute_dtype = "bfloat16"
    x = jnp.asarray(rng.uniform(0, 255, size=(5, 13, 13, 3)).astype(np.float32))
    want = np.asarray(pooler.apply_batch(rectifier.apply_batch(
        conv.apply_batch(x))))
    got = np.asarray(conv.apply_with([rectifier, pooler], x))
    # bfloat16 operands (8 bits) with float32 sums: a hundredth, not 1e-5.
    assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()
    assert np.abs(got - want).max() > 1e-6 * np.abs(want).max()


def test_the_pool_layout_puts_positions_of_one_owner_side_by_side():
    from keystone_tpu.ops.conv_pool_pallas import _pool_layout, _tiles

    ph, pw, regions, groups = _pool_layout(27, 27, 14, 13)
    assert (ph, pw) == (2, 2) and len(regions) == 9
    assert sum((r1 - r0) * (c1 - c0) for r0, r1, c0, c1 in regions) == 729
    # 92 groups of eight positions; one add a window owning all eight.
    assert len(groups) == 92
    whole = sum(1 for g in groups if len(g) == 1 and g[0][1] == 255)
    assert whole >= 76
    # Every position is summed into each window that owns it: 4 x 14 x 14.
    assert sum(bin(bits).count("1") for g in groups for _w, bits in g) == 4 * 196
    # 3 / 3 on 8: the last row and column belong to no window and are cut.
    ph, pw, regions, groups = _pool_layout(8, 8, 3, 3)
    assert (ph, pw) == (2, 2)
    assert sum((r1 - r0) * (c1 - c0) for r0, r1, c0, c1 in regions) == 36
    # cifar-fit's sizes: eight images a step, 79 lane tiles in 8 steps of 10.
    assert _tiles(6250, 736, 128, 10112, 4) == (8, 1280)
    assert _tiles(5, 64, 128, 128, 4) == (5, 128)
