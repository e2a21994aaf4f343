"""``random_patch_cifar.fit`` and the mechanisms it forced: a convolver whose
folded, patch-normalising filters are arguments of the chain's program, a
fused chain that runs row tile by row tile inside its one program where
its intermediates would not fit, and a convolver that takes the rectifier
and the pooler behind it into one kernel, so that there is no such
intermediate.

Tiny widths on the CPU (64 filters, 8 x 8 patch positions). The plain
reference is this file's own: numpy float64, explicit patches, the
equations of the benchmark's ``cifar-random-patch-10k`` configuration.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.config import config
from keystone_tpu.nodes.images import (
    Convolver,
    GrayScaler,
    ImageVectorizer,
    Pooler,
    SymmetricRectifier,
)
from keystone_tpu.nodes.images.external.fisher_vector import FisherVector
from keystone_tpu.nodes.images.external.sift import SIFTExtractor
from keystone_tpu.nodes.learning.block_least_squares import (
    BlockLeastSquaresEstimator,
    BlockLinearMapper,
)
from keystone_tpu.nodes.learning.pca import PCATransformer
from keystone_tpu.nodes.learning.zca import ZCAWhitener
from keystone_tpu.nodes.stats import CosineRandomFeatures, SignedHellingerMapper
from keystone_tpu.nodes.stats.normalizer import L2Normalizer
from keystone_tpu.nodes.stats.scalers import StandardScalerModel
from keystone_tpu.pipelines.images import random_patch_cifar as cifar
from keystone_tpu.utils.metrics import recorded_tracer, reset_tracer
from keystone_tpu.utils.stats import normalize_rows
from keystone_tpu.workflow import FusedTransformer
from keystone_tpu.workflow import pipeline as pipeline_module

CONF = cifar.RandomPatchCifarConfig(
    num_filters=64, patch_size=6, patch_sample=2000, patch_norm=10.0,
    pool_size=4, pool_stride=4, alpha=0.25, lam=30.0, block_size=96,
    num_iters=1, num_classes=5, seed=3)
SIDE, ROWS = 13, 160  # 8 x 8 positions, 2 x 2 windows: 512 features, 5 blocks + 32


def _images(seed, n=ROWS, side=SIDE, classes=5):
    """Class-textured images in [0, 255]: a sinusoid whose frequency is the
    class's, over noise."""
    r = np.random.default_rng(seed)
    y = r.integers(0, classes, size=n)
    u = np.arange(side)[None, :, None, None] * (0.4 + 0.3 * y[:, None, None, None])
    v = np.arange(side)[None, None, :, None] * (0.9 - 0.1 * y[:, None, None, None])
    x = 127.5 + 60 * np.sin(u + v + r.uniform(0, 6, size=(n, 1, 1, 3)))
    x = x + 25 * r.normal(size=(n, side, side, 3))
    return np.clip(x, 0, 255).astype(np.float32), y.astype(np.int32)


def _gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# ------------------------------------------------------ the plain reference


def _im2col(x, p):
    """(n, oh, ow, p p c): every p x p patch, flattened (row, column, channel)."""
    n, h, w, c = x.shape
    out = np.empty((n, h - p + 1, w - p + 1, p * p * c), x.dtype)
    for i in range(h - p + 1):
        for j in range(w - p + 1):
            out[:, i, j] = x[:, i:i + p, j:j + p, :].reshape(n, -1)
    return out


def _normalise(p, alpha):
    p = p - p.mean(axis=-1, keepdims=True)
    var = (p * p).sum(axis=-1, keepdims=True) / (p.shape[-1] - 1)
    return p / np.sqrt(var + alpha)


def _reference_filters(conf, x):
    """(f, M, mu): the unit whitened-patch filters, the ZCA map and mean."""
    n, h, w, _c = x.shape
    p = conf.patch_size
    rng = np.random.default_rng(conf.seed)
    img = rng.integers(0, n, size=conf.patch_sample)
    tops = rng.integers(0, h - p + 1, size=conf.patch_sample)
    lefts = rng.integers(0, w - p + 1, size=conf.patch_sample)
    patches = np.stack([x[i, t:t + p, l:l + p, :].reshape(-1)
                        for i, t, l in zip(img, tops, lefts)])
    patches = _normalise(patches, conf.patch_norm)
    mu = patches.mean(axis=0)
    cov = (patches - mu).T @ (patches - mu) / len(patches)
    lam, V = np.linalg.eigh(cov)
    M = (V / np.sqrt(lam + conf.zca_eps)) @ V.T
    idx = np.random.default_rng(conf.seed + 1).choice(
        len(patches), size=conf.num_filters, replace=False)
    f = (patches[idx] - mu) @ M
    return f / np.linalg.norm(f, axis=1, keepdims=True), M, mu


def _reference_features(conf, x, f, M, mu):
    patches = _normalise(_im2col(x, conf.patch_size), conf.patch_norm)
    z = ((patches - mu) @ M) @ f.T  # (n, oh, ow, F)
    z = np.concatenate([np.maximum(z - conf.alpha, 0), np.maximum(-z - conf.alpha, 0)], -1)
    s, w = conf.pool_stride, conf.pool_size
    starts = range(0, z.shape[1] - w + 1, s)
    pooled = np.stack([np.stack([z[:, a:a + w, b:b + w].sum(axis=(1, 2)) for b in starts], 1)
                       for a in starts], 1)
    return pooled.reshape(len(x), -1)


def _block_descent(A, Y, block, lam, epochs=1):
    """Block coordinate descent in block order, a dense ridge solve a visit,
    the last block at its true width. A and Y are centred here."""
    a_mean, y_mean = A.mean(axis=0), Y.mean(axis=0)
    A, R = A - a_mean, Y - y_mean
    blocks = [(s, min(s + block, A.shape[1])) for s in range(0, A.shape[1], block)]
    W = np.zeros((A.shape[1], Y.shape[1]))
    for _ in range(epochs):
        for s, e in blocks:
            Ab = A[:, s:e]
            R = R + Ab @ W[s:e]
            W[s:e] = np.linalg.solve(Ab.T @ Ab + lam * np.eye(e - s), Ab.T @ R)
            R = R - Ab @ W[s:e]
    return W, y_mean - a_mean @ W


def _reference_fit(conf, x, y, held_out):
    x, held_out = x.astype(np.float64), held_out.astype(np.float64)
    f, M, mu = _reference_filters(conf, x)
    train = _reference_features(conf, x, f, M, mu)
    mean, std = train.mean(axis=0), train.std(axis=0, ddof=1)
    Y = 2.0 * np.eye(conf.num_classes)[y] - 1.0
    W, b = _block_descent((train - mean) / std, Y, conf.block_size, conf.lam, conf.num_iters)
    features = _reference_features(conf, held_out, f, M, mu)
    return features, (features - mean) / std @ W + b


def _stages(fitted):
    out = []
    for t in fitted.transformers():
        out.extend(getattr(t, "stages", [t]))
    return out


# ------------------------------------------------- fit against the reference


@pytest.mark.parametrize("seed", [5, 6])
def test_fit_matches_the_plain_reference(seed):
    x, y = _images(seed)
    held_out, _ = _images(seed + 100, n=24)
    fitted = cifar.fit(CONF, x, y)
    stages = _stages(fitted)
    assert [type(s).__name__ for s in stages] == [
        "Convolver", "SymmetricRectifier", "Pooler", "ImageVectorizer",
        "StandardScalerModel", "BlockLinearMapper", "MaxClassifier"]
    mapper = stages[5]
    assert list(mapper.blocks) == [(s, min(s + 96, 512)) for s in range(0, 512, 96)]
    want_features, want_scores = _reference_fit(CONF, x, y, held_out)
    features = FusedTransformer(stages[:4]).batch_call(jnp.asarray(held_out))
    assert _gap(features, want_features) < 2e-5
    scores = FusedTransformer(stages[:6]).batch_call(jnp.asarray(held_out))
    assert _gap(scores, want_scores) < 1e-4
    predicted = np.asarray(fitted(held_out).get())
    assert (predicted == want_scores.argmax(axis=1)).mean() > 0.9


def test_fit_has_the_root_span_and_its_children():
    prior = config.trace
    config.trace = True
    reset_tracer()
    try:
        x, y = _images(7)
        cifar.fit(CONF, x, y)
        spans = recorded_tracer().spans()
    finally:
        config.trace = prior
        reset_tracer()
    (root,) = [s for s in spans if s["name"] == "fit" and s.get("parent_id") is None]
    assert root["args"]["pipeline"] == "cifar" and root["args"]["rows"] == ROWS
    assert root["args"]["closure_program_calls"] == 0
    by_name = {s["name"]: s for s in spans if s.get("root_id") == root["id"]}
    assert by_name["filters.fit"]["parent_id"] == root["id"]
    assert by_name["filters.fit"]["args"].items() >= {
        "patches": 2000, "dim": 108, "filters": 64}.items()
    conv = by_name["features.conv"]
    assert conv["parent_id"] == root["id"]
    assert conv["args"]["rows"] == ROWS and conv["args"]["filters"] == 64
    # The chain's arguments: the folded filters and the bias, to the byte.
    assert conv["args"]["bytes"] == (64 * 108 + 64) * 4
    assert {"solver.stack", "solver.epochs"} <= set(by_name)
    assert "solver.factor" not in by_name  # one epoch: no cached inverses


# ------------------------------------------------------------ the convolver


def test_folded_normalising_convolver_matches_explicit_patches(rng):
    x = rng.uniform(0, 255, size=(6, 11, 11, 3)).astype(np.float32)
    x[0] = 200.0  # a flat image: every patch's variance is nought
    f = rng.normal(size=(16, 6, 6, 3)).astype(np.float32)
    mu = rng.normal(size=108).astype(np.float32) * 0.1
    S = rng.normal(size=(108, 108))
    M = (S @ S.T / 108 + np.eye(108)).astype(np.float32)
    conv = Convolver(f, whitener=ZCAWhitener(M, mu), normalize_patches=10.0)
    patches = _normalise(_im2col(x.astype(np.float64), 6), 10.0)
    want = ((patches - mu) @ M.astype(np.float64)) @ f.reshape(16, -1).T.astype(np.float64)
    got = conv.batch_call(jnp.asarray(x))
    assert got.shape == (6, 6, 6, 16)
    assert _gap(got, want) < 1e-5
    np.testing.assert_allclose(np.asarray(got)[0], want[0], atol=1e-4)
    # Filters and bias are arguments of the program, and no constant in it.
    text = conv._jitted().lower(jnp.asarray(x)).as_text()
    assert "jit_apply_Convolver" in text and conv.shares_program()
    sizes = [int(np.prod([int(d) for d in shape.split("x")[:-1]] or [1])) for shape in
             re.findall(r"stablehlo\.constant[^\n]*?: tensor<([^>]*)>", text)]
    assert max(sizes) == 1  # scalars only


def test_an_unnormalised_convolver_keeps_its_arithmetic(rng):
    x = rng.normal(size=(4, 9, 9, 3)).astype(np.float32)
    f = rng.normal(size=(8, 6, 6, 3)).astype(np.float32)
    want = _im2col(x.astype(np.float64), 6) @ f.reshape(8, -1).T.astype(np.float64)
    plain = Convolver(f)
    assert plain.bias is None and plain.normalize_patches is None
    assert _gap(plain.batch_call(jnp.asarray(x)), want) < 1e-6


def test_normalize_rows_is_upstreams(rng):
    X = rng.normal(size=(7, 108)).astype(np.float32) * 30 + 100
    np.testing.assert_allclose(np.asarray(normalize_rows(jnp.asarray(X), 10.0)),
                               _normalise(X.astype(np.float64), 10.0), rtol=2e-5, atol=2e-6)


def test_pooling_14_by_13_on_27_is_two_by_two_explicit_slices(rng):
    z = rng.normal(size=(3, 27, 27, 5)).astype(np.float32)
    got = np.asarray(Pooler(13, 14, mode="sum").batch_call(jnp.asarray(z)))
    assert got.shape == (3, 2, 2, 5)
    for a, (r0, r1) in enumerate([(0, 14), (13, 27)]):
        for b, (c0, c1) in enumerate([(0, 14), (13, 27)]):
            np.testing.assert_allclose(
                got[:, a, b], z[:, r0:r1, c0:c1].astype(np.float64).sum(axis=(1, 2)),
                rtol=1e-5, atol=1e-4)


# ----------------------------------------------------- a chain in row tiles


def _conv_chain(rng, filters=16, mode="max"):
    """Max pooling: the stages run one by one and the rectifier's output is
    an intermediate (sum pooling runs inside the convolver's kernel: the
    chains further down)."""
    f = rng.normal(size=(filters, 6, 6, 3)).astype(np.float32)
    return FusedTransformer([
        Convolver(f, normalize_patches=10.0), SymmetricRectifier(alpha=0.25),
        Pooler(4, 4, mode=mode), ImageVectorizer()])


def _budget(monkeypatch, nbytes):
    """The device's memory as the tile rule sees it."""
    from keystone_tpu.utils import metrics

    monkeypatch.setattr(metrics, "device_hbm_bytes", lambda default=None: int(nbytes))


def test_a_tiled_chain_equals_the_untiled_one(rng, monkeypatch):
    x = jnp.asarray(rng.uniform(0, 255, size=(37, 13, 13, 3)).astype(np.float32))
    chain = _conv_chain(rng)
    assert chain.fused_stages == 0 and not chain.uses_pallas
    assert chain.row_tiling(x) is None
    untiled = np.asarray(chain._apply_stages(x))  # stage by stage, no program
    # The rectifier's output is 8 x 8 x 32 floats a row: room for 5 rows.
    # (The device's memory is no part of a program's key: this shape's
    # first trace is the one below.)
    _budget(monkeypatch, 8 * 5 * 8 * 8 * 32 * 4 + 8)
    assert chain.row_tiling(x) == (5, 8)  # 37 rows: 8 tiles of 5, 3 pad rows
    assert "stablehlo.while" in chain._jitted().lower(x).as_text()
    tiled = np.asarray(chain.batch_call(x))
    assert tiled.shape == untiled.shape == (37, 2 * 2 * 32)
    np.testing.assert_allclose(tiled, untiled, rtol=1e-6, atol=1e-5)
    # The walk's span of a tiled chain says so.
    prior = config.trace
    config.trace = True
    reset_tracer()
    try:
        pipeline = chain.stages[0].and_then(chain.stages[1]).and_then(
            chain.stages[2]).and_then(chain.stages[3])
        np.testing.assert_array_equal(np.asarray(pipeline(x).get()), tiled)
        spans = recorded_tracer().spans()
    finally:
        config.trace = prior
        reset_tracer()
    (node,) = [s for s in spans if s["name"].startswith("node:Fused(Convolver")]
    assert (node["args"]["tile_rows"], node["args"]["tiles"]) == (5, 8)
    assert "fused_stages" not in node["args"]  # no stage took another


def test_the_tile_rule(rng, monkeypatch):
    chain = _conv_chain(rng)
    x = jax.ShapeDtypeStruct((1000, 13, 13, 3), jnp.float32)
    row = 8 * 8 * 32 * 4  # the largest intermediate, a row
    _budget(monkeypatch, 8 * 1000 * row)
    assert chain.row_tiling(x) is None  # it fits, to the byte
    _budget(monkeypatch, 8 * 1000 * row - 8)
    assert chain.row_tiling(x) == (500, 2)  # over: balanced tiles
    _budget(monkeypatch, 8 * 64 * row)
    assert chain.row_tiling(x) == (63, 16)  # 1000 rows in 16 tiles of at most 64
    _budget(monkeypatch, 8)
    assert chain.row_tiling(x) == (1, 1000)  # never under a row
    # The last stage's output is written whole, tiles or none: no intermediate.
    assert FusedTransformer(chain.stages[:1] + chain.stages[3:]).row_tiling(x) == (1, 1000)
    single = FusedTransformer([chain.stages[0]])
    assert single.row_tiling(x) is None
    # A stage that couples rows: nothing may be cut.
    coupled = _conv_chain(rng)
    coupled.row_independent = False
    assert coupled.row_tiling(x) is None
    # A chain that keeps the closure path (a callable field) is jitted as
    # it always was.
    closure = _conv_chain(rng)
    closure.stages[1].hook = lambda: None
    assert not closure.shares_program() and closure.row_tiling(x) is None


V5E_HBM = int(15.75 * 2**30)


def _imagenet_branch():
    k, d = 256, 64
    mixture = (np.full(k, 1 / k, np.float32), np.zeros((k, d), np.float32),
               np.ones((k, d), np.float32))
    return FusedTransformer([
        GrayScaler(), SIFTExtractor(step=4, bin_size=4, backend="xla"),
        PCATransformer(np.zeros((128, d), np.float32), np.zeros(128, np.float32)),
        FisherVector(*mixture, backend="tpu"), SignedHellingerMapper(), L2Normalizer()])


def _timit_chain():
    cosines = CosineRandomFeatures(np.zeros((440, 8), np.float32), np.zeros(8, np.float32))
    cosines.W = jax.ShapeDtypeStruct((440, 163840), jnp.float32)  # shapes price a chain
    cosines.b = jax.ShapeDtypeStruct((163840,), jnp.float32)
    return FusedTransformer([
        StandardScalerModel(np.zeros(440, np.float32), np.ones(440, np.float32)), cosines])


def _cifar_chain(filters=10000):
    conv = Convolver(np.zeros((8, 6, 6, 3), np.float32), normalize_patches=10.0)
    conv.filters = jax.ShapeDtypeStruct((filters, 6, 6, 3), jnp.float32)
    conv.bias = jax.ShapeDtypeStruct((filters,), jnp.float32)
    conv.num_filters = filters
    return FusedTransformer([conv, SymmetricRectifier(alpha=0.25),
                             Pooler(13, 14, mode="sum"), ImageVectorizer()])


@pytest.mark.parametrize("chain, rows, module, tiling", [
    (_imagenet_branch, (8192, 64, 64, 3),
     "jit_apply_GrayScaler_SIFTExtractor_PCATransformer_FisherVector_"
     "SignedHellingerMapper_L2Normalizer", None),
    (_timit_chain, (4096, 440),
     "jit_apply_StandardScalerModel_CosineRandomFeatures", None),
    (_cifar_chain, (6250, 32, 32, 3),
     "jit_apply_Convolver_SymmetricRectifier_Pooler_ImageVectorizer", (1563, 4)),
    (lambda: _cifar_chain(512), (50000, 32, 32, 3),
     "jit_apply_Convolver_SymmetricRectifier_Pooler_ImageVectorizer", (2632, 19)),
], ids=["imagenet-fit", "timit-fit", "cifar-fit", "cifar-kernel-fit"])
def test_the_cells_chains_at_their_sizes(chain, rows, module, tiling, monkeypatch):
    """At the benchmark's cell sizes on a v5e's memory two cells' chains
    stay whole. The convolver's chains run in row tiles: the responses
    (58 MB a row at 10,000 filters) stay inside the kernel, but the explicit
    patches it is handed are 750 KB a row at any filter count, 37.5 GB for
    ``cifar-kernel-fit``'s 50,000 rows, and with the pooled sums (320 KB a
    row in ``cifar-fit``, 16 KB in ``cifar-kernel-fit``) they are what the
    step holds: 2.11 GB a tile. The benchmark's metric files filter on the
    module names."""
    _budget(monkeypatch, V5E_HBM)
    chain = chain()
    assert chain.row_tiling(jax.ShapeDtypeStruct(rows, jnp.float32)) == tiling
    assert pipeline_module._program(chain._program_name()).__wrapped__.__name__ == (
        module[len("jit_"):])


def test_the_cifar_chain_is_priced_with_its_kernels_patches(monkeypatch):
    """What the tile rule prices in ``cifar-fit``'s chain is what the
    convolver's step holds, a row: the pooled sums (2 x 2 x 20,000 floats)
    and the patches laid out for the kernel, as cut (27 x 27 positions of
    128 lanes) and in the kernel's order (736 positions)."""
    chain = _cifar_chain()
    assert chain.fused_stages == 3
    x = jax.ShapeDtypeStruct((6250, 32, 32, 3), jnp.float32)
    row = (2 * 2 * 20000 + 27 * 27 * 128 + 736 * 128) * 4
    _budget(monkeypatch, 8 * 6250 * row)
    assert chain.row_tiling(x) is None
    _budget(monkeypatch, 8 * 6250 * row - 8)
    assert chain.row_tiling(x) == (3125, 2)
    # A stage that runs alone lays nothing out: the walk of the same
    # stages one by one is priced by its outputs, as before.
    walked = FusedTransformer(chain.stages[:1] + chain.stages[2:])  # no rectifier
    assert walked.fused_stages == 0
    _budget(monkeypatch, 8 * 6250 * 27 * 27 * 10000 * 4)
    assert walked.row_tiling(x) is None


# ---------------------- a convolver that takes its rectifier and its pooler


def _walked(stages, x):
    for stage in stages:
        x = stage.apply_batch(x)
    return x


def _lowered_for_tpu(chain, x, monkeypatch):
    """The chain's program as it is lowered for a TPU (no device needed to
    lower, and none to see whether a Mosaic kernel is in it), at a row
    count no test ran: a trace made on the CPU holds the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    program = pipeline_module._program(chain._program_name())
    x = jax.ShapeDtypeStruct((3,) + x.shape[1:], x.dtype)
    return program.trace(chain, x).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_a_chain_with_the_triple_equals_its_stage_walk(rng, mode, monkeypatch):
    from keystone_tpu.utils.metrics import sharding_counters

    x = jnp.asarray(rng.uniform(0, 255, size=(21, 13, 13, 3)).astype(np.float32))
    chain = _conv_chain(rng, filters=32, mode=mode)
    assert chain.fused_stages == 3 and chain.uses_pallas
    # The stage list is what it was: the program's name, the signature and
    # the hashes of a chain say nothing of who runs what.
    assert [type(s).__name__ for s in chain.stages] == [
        "Convolver", "SymmetricRectifier", "Pooler", "ImageVectorizer"]
    assert chain._program_name() == "Convolver_SymmetricRectifier_Pooler_ImageVectorizer"
    assert chain.signature() == ("fused",) + tuple(s.signature() for s in chain.stages)
    h = 12345
    for stage in chain.stages:
        h = stage.chain_hash(h)
    assert chain.chain_hash(12345) == h
    unfused = chain.stages[0].and_then(chain.stages[1]).and_then(
        chain.stages[2]).and_then(chain.stages[3])
    want = np.asarray(_walked(chain.stages, x))
    before = sharding_counters.snapshot().get("pallas_interpret_calls", 0)
    got = np.asarray(chain.batch_call(x))
    # Counted where it is traced: by the tile rule's pricing and the program.
    assert sharding_counters.snapshot()["pallas_interpret_calls"] > before
    assert got.shape == want.shape == (21, 2 * 2 * 64)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    # The pooled sums are the one intermediate; the filters and the bias
    # are still arguments, and the kernel is a call of the chain's module.
    (pooled,) = jax.eval_shape(pipeline_module._intermediates, chain, x)
    assert pooled.shape == (21, 2, 2, 64)
    text = _lowered_for_tpu(chain, x, monkeypatch)
    assert "jit_apply_Convolver_SymmetricRectifier_Pooler_ImageVectorizer" in text
    assert text.count("tpu_custom_call") == 1
    assert "tensor<32x6x6x3xf32>" in re.search(r"func\.func public @main\(([^\n]*)", text).group(1)
    # The walk's span of such a chain says so.
    monkeypatch.undo()
    prior = config.trace
    config.trace = True
    reset_tracer()
    try:
        np.testing.assert_array_equal(np.asarray(unfused(x).get()), got)
        spans = recorded_tracer().spans()
    finally:
        config.trace = prior
        reset_tracer()
    (node,) = [s for s in spans if s["name"].startswith("node:Fused(Convolver")]
    assert node["args"]["fused_stages"] == 3
    assert "tiles" not in node["args"]


def _between(rng):
    chain = _conv_chain(rng, mode="sum")
    return FusedTransformer(
        chain.stages[:2] + [SymmetricRectifier(alpha=0.0)] + chain.stages[2:])


@pytest.mark.parametrize("chain", [
    lambda rng: _conv_chain(rng, mode="max"),  # a pooling the kernel has not
    _between,  # a stage between the rectifier and the pooler
    lambda rng: FusedTransformer(_conv_chain(rng, mode="sum").stages[:2]),  # no pooler
    lambda rng: FusedTransformer(  # a lone convolver in front of another stage
        _conv_chain(rng, mode="sum").stages[:1] + [ImageVectorizer()]),
], ids=["max-pooling", "a-stage-between", "no-pooler", "lone-convolver"])
def test_any_other_neighbourhood_walks_stage_by_stage(rng, chain, monkeypatch):
    from keystone_tpu.utils.metrics import sharding_counters

    chain = chain(rng)
    x = jnp.asarray(rng.uniform(0, 255, size=(6, 13, 13, 3)).astype(np.float32))
    assert chain.fused_stages == 0 and not chain.uses_pallas
    before = sharding_counters.snapshot()
    got = np.asarray(chain.batch_call(x))
    assert sharding_counters.snapshot() == before  # no kernel was traced
    # Today's arithmetic, bit for bit.
    np.testing.assert_array_equal(got, np.asarray(jax.jit(
        lambda x: _walked(chain.stages, x))(x)))
    assert "tpu_custom_call" not in _lowered_for_tpu(chain, x, monkeypatch)


def test_on_a_mesh_a_shards_rows_take_the_one_device_lowering(rng):
    """``apply_sharded`` runs every shard's rows through ``apply_batch``
    under ``shard_map`` (since PR 38): the convolver takes its rectifier
    and pooler on a mesh as on one device, and the kernel is traced (until
    then a chain on a mesh was walked stage by stage)."""
    from keystone_tpu.utils.mesh import SpecLayout
    from keystone_tpu.utils.metrics import sharding_counters

    chain = _conv_chain(rng, mode="sum")
    layout = SpecLayout.for_mesh()
    x = rng.uniform(0, 255, size=(2 * layout.num_shards, 13, 13, 3)).astype(np.float32)
    before = sharding_counters.snapshot().get("pallas_interpret_calls", 0)
    got = layout.jit(lambda x: chain.apply_sharded(x, layout))(layout.put(x))
    assert sharding_counters.snapshot().get("pallas_interpret_calls", 0) > before
    want = np.asarray(_walked(chain.stages, jnp.asarray(x)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-4)


# ------------------------------------------- one epoch, a ragged last block


def test_one_epoch_with_a_ragged_block_is_a_dense_ridge_solve_a_visit(rng):
    n, d, k, block, lam = 96, 80, 3, 32, 3.0  # blocks 32, 32, 16
    A = rng.normal(size=(n, d)).astype(np.float32)
    A[:, 40:] += 0.5 * A[:, :40]  # the blocks are not orthogonal
    Y = rng.normal(size=(n, k)).astype(np.float32)
    mapper = BlockLeastSquaresEstimator(block_size=block, num_iters=1, lam=lam).fit(A, Y)
    assert isinstance(mapper, BlockLinearMapper)
    assert list(mapper.blocks) == [(0, 32), (32, 64), (64, 80)]
    assert [w.shape for w in mapper.W_blocks] == [(32, 3), (32, 3), (16, 3)]
    W, b = _block_descent(A.astype(np.float64), Y.astype(np.float64), block, lam)
    assert _gap(np.concatenate([np.asarray(w) for w in mapper.W_blocks]), W) < 1e-5
    X = rng.normal(size=(8, d)).astype(np.float32)
    assert _gap(mapper.batch_call(jnp.asarray(X)), X @ W + b) < 1e-5
