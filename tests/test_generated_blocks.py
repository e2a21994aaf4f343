"""The generated-block solve (``linalg/bcd.py``: a ``BlockSource`` in place
of the feature matrix) and the optimizer's rewrite that hands it gathered
branches of one chain (``workflow/rules.GeneratedBlocksRule``), against the
stored-block solve and a plain float64 reference, at tiny widths on the
8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.config import config
from keystone_tpu.linalg import BlockSource, bcd
from keystone_tpu.linalg.row_matrix import _blocks_kept
from keystone_tpu.loaders import MnistLoader
from keystone_tpu.nodes.learning.block_least_squares import (
    BlockLeastSquaresEstimator,
    BlockLinearMapper,
)
from keystone_tpu.nodes.stats import LinearRectifier, PaddedFFT, RandomSignNode
from keystone_tpu.utils.metrics import recorded_tracer, reset_tracer
from keystone_tpu.workflow import Pipeline
from keystone_tpu.workflow.pipeline import BranchStack


def _chains(d_in, branches, seed=0, alpha=0.0):
    return [[RandomSignNode.create(d_in, seed=seed + i), PaddedFFT(),
             LinearRectifier(alpha=alpha)] for i in range(branches)]


def _stack(d_in, branches, block):
    return BranchStack.of(_chains(d_in, branches), block,
                          jax.ShapeDtypeStruct((1, d_in), jnp.float32))


def _problem(seed, n=100, d_in=12, k=3):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, d_in)).astype(np.float32),
            rng.normal(size=(n, k)).astype(np.float32))


def _gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def test_the_stack_is_the_gather():
    """Six branches of 12 pixels (8 features a branch), stacked for blocks
    of two branches: the gather's columns, and each block its slice."""
    X, _ = _problem(1)
    stack = _stack(12, 6, 16)
    assert (stack.branches, stack.per_block, stack.width) == (6, 2, 8)
    gathered = np.concatenate(
        [np.asarray(c[2](c[1](c[0](X)))) for c in _chains(12, 6)], axis=1)
    np.testing.assert_array_equal(np.asarray(stack.apply_batch(jnp.asarray(X))), gathered)
    for b in range(3):
        np.testing.assert_array_equal(
            np.asarray(stack.block(jnp.asarray(X), b)), gathered[:, 16 * b:16 * (b + 1)])
    # A ragged last block: five branches, the third block one branch and zeros.
    ragged = _stack(12, 5, 16)
    last = np.asarray(ragged.block(jnp.asarray(X), 2))
    np.testing.assert_array_equal(last[:, :8], gathered[:, 32:40])
    assert not last[:, 8:].any()


def test_unlike_branches_do_not_stack():
    x = jax.ShapeDtypeStruct((1, 12), jnp.float32)
    unlike = _chains(12, 2) + _chains(12, 1, seed=9, alpha=0.5)
    assert BranchStack.of(unlike, 16, x) is None  # a static field differs
    assert BranchStack.of(_chains(12, 2) + [_chains(12, 1)[0][:2]], 16, x) is None
    assert BranchStack.of(_chains(12, 4), 12, x) is None  # 8 does not divide 12


@pytest.mark.parametrize("branches", [6, 5], ids=["whole", "ragged"])
@pytest.mark.parametrize("keep", ["all", 0, 1])
def test_the_generated_solve_is_the_stored_solve(monkeypatch, keep, branches):
    """Three epochs over blocks of two branches, every block kept, none or
    one: the weights, the intercept and the residual are the solve's on the
    stored features, and a block made again is the block the first epoch
    made, bit for bit (the weights do not move by one bit with the store)."""
    X, Y = _problem(2)
    stack = _stack(12, branches, 16)
    dim = 8 * branches
    features = np.asarray(stack.apply_batch(jnp.asarray(X)))
    est = BlockLeastSquaresEstimator(16, num_iters=3, lam=0.5)
    stored = est.fit(features, Y)

    def generated(slots):
        bcd._generated_solve_fn.cache_clear()
        if slots != "all":
            monkeypatch.setattr(bcd, "_blocks_kept", lambda *sizes, **kw: slots)
        return est.fit(BlockSource(jnp.asarray(X), stack, 16, dim), Y)

    made = generated(keep)
    assert made.blocks == stored.blocks
    assert _gap(made.W, stored.W) < 1e-5 and _gap(made.b, stored.b) < 1e-5
    residual = Y - np.asarray(stored(features))
    np.testing.assert_allclose(Y - np.asarray(made(jnp.asarray(X))), residual,
                               atol=1e-4 * np.abs(residual).max())
    every = generated("all") if keep != "all" else made
    np.testing.assert_array_equal(np.asarray(made.W), np.asarray(every.W))


def test_a_block_made_again_is_bit_for_bit_the_first():
    """``block`` in the first epoch's program and in another program reads
    the same bits."""
    X, _ = _problem(3)
    stack = _stack(12, 6, 16)

    @jax.jit
    def twice(stack, X):
        return stack.block(X, 1), jnp.where(X[:, :1] > -99, stack.block(X, 1), 0.0)

    once, again = twice(stack, jnp.asarray(X))
    alone = jax.jit(lambda s, x, b: s.block(x, b))(stack, jnp.asarray(X), 1)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(again))
    np.testing.assert_array_equal(np.asarray(once), np.asarray(alone))


def test_the_span_counts_the_blocks_made(monkeypatch):
    monkeypatch.setattr(config, "trace", True)
    monkeypatch.setattr(bcd, "_blocks_kept", lambda *sizes, **kw: 1)
    bcd._generated_solve_fn.cache_clear()
    reset_tracer()
    X, Y = _problem(4)
    BlockLeastSquaresEstimator(16, num_iters=3, lam=0.5).fit(
        BlockSource(jnp.asarray(X), _stack(12, 6, 16), 16, 48), Y)
    span = [s for s in recorded_tracer().spans() if s["name"] == "bcd.generated"][-1]
    reset_tracer()
    rows = 112  # 100 rows padded to the fold's multiple (16 blocks, 8 shards)
    assert {k: span["args"][k] for k in (
        "generated_blocks", "kept_blocks", "kept_bytes", "branches", "block", "epochs")} == {
        "generated_blocks": 3 + 2 * 2, "kept_blocks": 1, "kept_bytes": rows * 16 * 4,
        "branches": 6, "block": 16, "epochs": 3}


def test_the_generated_map_applies_as_the_dense_map():
    X, _ = _problem(5, n=37)
    stack = _stack(12, 5, 16)
    rng = np.random.default_rng(5)
    blocks = [(0, 16), (16, 32), (32, 40)]
    W = [rng.normal(size=(e - s, 4)).astype(np.float32) for s, e in blocks]
    b = rng.normal(size=(4,)).astype(np.float32)
    dense = BlockLinearMapper(W, blocks, b)(stack.apply_batch(jnp.asarray(X)))
    made = BlockLinearMapper(W, blocks, b, generator=stack)(jnp.asarray(X))
    np.testing.assert_allclose(np.asarray(made), np.asarray(dense), rtol=1e-5, atol=1e-5)


# (one shard's rows, block, blocks, epochs, factor bytes) -> slots at a
# v5e's reported limit, float32, arguments of X 784 and Y 10 wide.
V5E = 16_909_336_064


@pytest.mark.parametrize("rows, blocks, epochs, factors, making, keep", [
    (60_000, 50, 3, 50 * 2048 * 2048 * 4, 0, 25),  # 0.84 GB of inverses
    # mnist-fft-fit: and what making a block holds, the block and the
    # rows, by the described v5e's compiler (tests/test_aot_tpu.py)
    (60_000, 50, 3, 50 * 2048 * 2048 * 4, 60_000 * (2048 + 784) * 4, 23),
    (60_000, 50, 3, 0, 0, 26),                      # the kernel solver's count
    (60_000, 50, 1, 50 * 2048 * 2048 * 4, 0, 0),    # one epoch keeps nothing
    (15_000, 50, 3, 50 * 2048 * 2048 * 4, 0, 50),   # a shard of four: all fit
])
def test_the_shared_rule_counts_the_cached_inverses(rows, blocks, epochs, factors,
                                                    making, keep):
    arguments = rows * (784 + 10) * 4
    assert _blocks_kept(rows, 2048, blocks, epochs, arguments, 4, V5E,
                        factor_bytes=factors, making_bytes=making) == keep


def test_making_a_block_counts_the_block_and_its_temporaries():
    """``bcd._making_bytes``: the generator compiled alone on this backend,
    at least the block it makes and the rows, once a shape."""
    stack = _stack(12, 4, 32)
    made = bcd._making_bytes(stack, 100, 12, jnp.float32)
    assert made >= 100 * (32 + 12) * 4
    assert bcd._making_bytes(_stack(12, 4, 32), 100, 12, jnp.float32) == made
    assert bcd._making_bytes_of.cache_info().hits >= 1


def _reference_scores(X, Y, held, chains, block, epochs, lam):
    """float64 numpy: the gathered features, centred, block Gauss-Seidel
    from zero in natural order, each block solved directly."""
    def feats(rows):
        out = []
        for signs, _fft, _rect in chains:
            z = rows.astype(np.float64) * np.asarray(signs.signs, np.float64)
            n = 1 << int(np.ceil(np.log2(z.shape[1])))
            f = np.fft.fft(np.pad(z, ((0, 0), (0, n - z.shape[1]))), axis=1)
            out.append(np.maximum(f[:, : n // 2].real, 0.0))
        return np.concatenate(out, axis=1)

    F, Yd = feats(X), Y.astype(np.float64)
    mean, ymean = F.mean(axis=0), Yd.mean(axis=0)
    A, R = F - mean, Yd - ymean
    W = np.zeros((F.shape[1], Y.shape[1]))
    for _ in range(epochs):
        for s in range(0, F.shape[1], block):
            a = A[:, s:s + block]
            R = R + a @ W[s:s + block]
            W[s:s + block] = np.linalg.solve(a.T @ a + lam * np.eye(a.shape[1]), a.T @ R)
            R = R - a @ W[s:s + block]
    return feats(held) @ W + (ymean - mean @ W)


def test_the_pipeline_fits_eight_ffts_by_generated_blocks():
    """MnistRandomFFT at 8 FFTs in blocks of two branches goes the
    generated way by its normal path (the fitted map makes the features
    itself) and scores as the plain reference does."""
    from keystone_tpu.pipelines.images import mnist_random_fft as mnist

    conf = mnist.MnistRandomFFTConfig(num_ffts=8, block_size=1024, num_epochs=3,
                                      synthetic_n=2048, seed=3)
    train, test = MnistLoader.synthetic(n=conf.synthetic_n, seed=conf.seed)
    fitted = mnist.fit(conf, train.data, train.labels)
    mapper = [t for t in fitted.transformers() if isinstance(t, BlockLinearMapper)]
    assert len(mapper) == 1 and isinstance(mapper[0].generator, BranchStack)
    assert mapper[0].generator.per_block == 2 and len(mapper[0].blocks) == 4
    held = test.data[:64]
    chains = _chains(784, 8, seed=conf.seed)
    Y = 2.0 * np.eye(10)[train.labels] - 1.0
    want = _reference_scores(train.data, Y, held, chains, 1024, 3, conf.lam)
    assert _gap(mapper[0](jnp.asarray(held)), want) < 1e-3
    np.testing.assert_array_equal(np.asarray(fitted(held).get()), want.argmax(axis=1))


def test_a_gather_of_unlike_branches_keeps_its_path():
    """Branches that are not one chain: the estimator is fit on the
    gathered features, the map applied to them, as before the rule."""
    X, Y = _problem(6)
    branches = [c[0].and_then(c[1]).and_then(c[2])
                for c in _chains(12, 2) + _chains(12, 1, seed=9, alpha=0.5)]
    est = BlockLeastSquaresEstimator(8, num_iters=2, lam=0.5)
    fitted = Pipeline.gather(branches).and_then(est, X, Y).fit()
    mapper = [t for t in fitted.transformers() if isinstance(t, BlockLinearMapper)]
    assert len(mapper) == 1 and mapper[0].generator is None
    features = np.concatenate([np.asarray(b.apply(X).get()) for b in branches], axis=1)
    direct = BlockLeastSquaresEstimator(8, num_iters=2, lam=0.5).fit(features, Y)
    np.testing.assert_allclose(np.asarray(fitted(X).get()),
                               np.asarray(direct(features)), rtol=1e-5, atol=1e-5)
