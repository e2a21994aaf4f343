"""RandomPatchCifar — the canonical CIFAR pipeline.

Ref: src/main/scala/pipelines/images/cifar/RandomPatchCifar.scala
(BASELINE.json config: "Convolver + ZCAWhitener + BlockLeastSquaresEstimator"):
random patches → patch normalisation → ZCA whitening → convolution with
whitened random-patch filters → symmetric rectification → spatial sum
pooling → StandardScaler → BlockLeastSquaresEstimator → MaxClassifier
(SURVEY.md §2.11, §3.1) [unverified].

Upstream's documented launch is ``--numFilters 10000 --lambda 3000`` with
100,000 patches for the whitener, patches normalised with 10.0 under the
root, pooling window 14 at stride 13 (2 x 2 windows on the 27 x 27
responses: 80,000 features), alpha 0.25 and one epoch at block 4096; the
benchmark's configuration ``cifar-random-patch-10k`` carries these. The
defaults below stay what a laptop runs (256 filters, 10,000 patches, one
13 x 13 window, three epochs, lambda 10, no patch normalisation). Upstream's
settings from the command line:

    bin/run-pipeline.sh RandomPatchCifar --num-filters 10000 \
        --patch-sample 100000 --patch-norm 10 --pool-size 14 --pool-stride 13 \
        --num-iters 1 --lam 3000

TPU notes: the filter fit (patch gather, normalisation, ZCA, filter draw)
stays on the device; conv + rectify + pool + vectorize is one XLA program
(MXU conv, vector-unit rectify, reduce_window pool) whose filters are its
arguments, and which runs row tile by row tile where the responses of all
rows would not fit (``FusedTransformer.row_tiling``); the solve is the
psum-reduced block coordinate descent.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
import numpy as np

from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.loaders.cifar import CifarLoader
from keystone_tpu.nodes.images import (
    Convolver,
    ImageVectorizer,
    Pooler,
    RandomPatcher,
    SymmetricRectifier,
)
from keystone_tpu.nodes.learning import (
    BlockLeastSquaresEstimator,
    ZCAWhitenerEstimator,
)
from keystone_tpu.nodes.stats import StandardScaler
from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
from keystone_tpu.utils.metrics import active_tracer, program_counters, span_of
from keystone_tpu.utils.stats import normalize_rows
from keystone_tpu.workflow import Pipeline


@dataclass
class RandomPatchCifarConfig:
    train_path: Optional[str] = None
    test_path: Optional[str] = None
    num_filters: int = 256
    patch_size: int = 6
    patch_sample: int = 10000
    # The offset under the root of upstream's patch normalisation
    # (normalizePatches = true: 10.0); None leaves patches as they are.
    patch_norm: Optional[float] = None
    pool_size: int = 13
    pool_stride: int = 13
    alpha: float = 0.25
    lam: float = 10.0
    block_size: int = 4096
    num_iters: int = 3
    zca_eps: float = 0.1
    num_classes: int = 10
    seed: int = 0
    synthetic_n: int = 2048
    # "bfloat16" runs the conv featurization on the MXU's bf16/f32-accum
    # path (features and the solve stay f32 unless KEYSTONE_SOLVER_DTYPE).
    feature_dtype: Optional[str] = None


def fit_convolver(conf: RandomPatchCifarConfig, train_images) -> Convolver:
    """The filter fit: random patches of the train images, normalised
    (``patch_norm``), ZCA-whitened; ``num_filters`` of the whitened patches,
    each scaled to unit length, are the filters, and the whitener is folded
    into them. Patch and filter indices are drawn on the host from the
    seed; every array stays on the device the images are on, and nothing
    here waits for it."""
    patches = RandomPatcher(
        num_patches=conf.patch_sample,
        patch_size=conf.patch_size,
        seed=conf.seed,
    )(train_images)
    flat = patches.reshape(patches.shape[0], -1)
    if conf.patch_norm is not None:
        flat = normalize_rows(flat, conf.patch_norm)
    whitener = ZCAWhitenerEstimator(eps=conf.zca_eps).fit(flat)
    # Sample num_filters whitened patches as filters, unit-normalized.
    rng = np.random.default_rng(conf.seed + 1)
    idx = rng.choice(flat.shape[0], size=conf.num_filters, replace=False)
    filt_flat = whitener(flat[idx])
    norms = jnp.linalg.norm(filt_flat, axis=1, keepdims=True)
    filt_flat = filt_flat / jnp.maximum(norms, 1e-8)
    filters = filt_flat.reshape(
        conf.num_filters, conf.patch_size, conf.patch_size, patches.shape[-1]
    )
    return Convolver(filters, whitener=whitener,
                     compute_dtype=conf.feature_dtype,
                     normalize_patches=conf.patch_norm)


def build_featurizer(conf: RandomPatchCifarConfig, train_images) -> Pipeline:
    """Fit filters (random whitened patches) and build the conv featurizer."""
    tracer = active_tracer()
    with span_of(tracer, "filters.fit", "pipeline", patches=conf.patch_sample,
                 dim=conf.patch_size ** 2 * int(train_images.shape[-1]),
                 filters=conf.num_filters):
        convolver = fit_convolver(conf, train_images)
    return (
        convolver
        .and_then(SymmetricRectifier(alpha=conf.alpha))
        .and_then(Pooler(conf.pool_stride, conf.pool_size, mode="sum"))
        .and_then(ImageVectorizer())
    )


def fit_features(conf: RandomPatchCifarConfig, train_images):
    """(featurizer, fitted scaler, the train rows' scaled features): what
    this pipeline and its kernel variant (``random_patch_cifar_kernel``)
    put in front of their heads."""
    rows = int(train_images.shape[0])
    tracer = active_tracer()
    featurizer = build_featurizer(conf, train_images)
    # The features are computed once, here, and handed on as data.
    # Nothing waits for them: the span covers the chain's dispatch.
    with span_of(tracer, "features.conv", "pipeline", rows=rows,
                 filters=conf.num_filters) as attrs:
        sent = program_counters.get("argument_bytes")
        features = featurizer(train_images).get()
        if attrs is not None:
            attrs["bytes"] = program_counters.get("argument_bytes") - sent
    scaler = StandardScaler().fit(features)
    # The scaled copy is what the solver reads.
    return featurizer, scaler, scaler(features)


def fit(conf: RandomPatchCifarConfig, train_images, train_labels) -> Pipeline:
    """Fit on the train images: the fitted pipeline, images in and the
    class index out (its stages: convolver, rectifier, pooler, vectorizer,
    scaler, block linear map, argmax). The one construction ``run`` (the
    CLI) and the benchmark share."""
    train_images = jnp.asarray(train_images)
    # The root span of one whole fit. It closes when the solver's programs
    # are dispatched, not when the device has run them. It carries how the
    # fit's transformer programs were found (``program_counters``): a
    # closure call is a program traced for this fit alone.
    with span_of(active_tracer(), "fit", "pipeline", pipeline="cifar",
                 rows=int(train_images.shape[0])) as root:
        calls = program_counters.calls()
        featurizer, scaler, scaled = fit_features(conf, train_images)
        targets = ClassLabelIndicators(conf.num_classes)(train_labels)
        head = BlockLeastSquaresEstimator(
            block_size=conf.block_size,
            num_iters=conf.num_iters,
            lam=conf.lam,
        ).with_data(scaled, targets)
        del scaled
        fitted = (featurizer.and_then(scaler).and_then(head)
                  .and_then(MaxClassifier()).fit())
        if root is not None:
            root.update(program_counters.since(calls))
        return fitted


def run(conf: RandomPatchCifarConfig, fit=fit) -> dict:
    """Load or make the data, ``fit`` on the train images (this module's,
    or the kernel variant's), predict the test images and evaluate."""
    if conf.train_path:
        if not conf.test_path:
            raise ValueError("--test is required when --train is given")
        train = CifarLoader.load(conf.train_path)
        test = CifarLoader.load(conf.test_path)
    else:
        train, test = CifarLoader.synthetic(n=conf.synthetic_n)

    t0 = time.perf_counter()
    pipeline = fit(conf, train.data, train.labels)
    predictions = pipeline(test.data).get()
    elapsed = time.perf_counter() - t0

    metrics = MulticlassClassifierEvaluator(conf.num_classes).evaluate(
        predictions, test.labels
    )
    return {
        "test_accuracy": metrics.total_accuracy,
        "macro_f1": metrics.macro_f1,
        "seconds": elapsed,
        "summary": metrics.summary(),
    }


def main(argv=None):
    from keystone_tpu.utils.platform import setup_platform

    setup_platform()
    p = argparse.ArgumentParser(
        description="RandomPatchCifar pipeline. The defaults are a laptop's; "
        "upstream's documented run is --num-filters 10000 --patch-sample "
        "100000 --patch-norm 10 --pool-size 14 --pool-stride 13 --num-iters 1 "
        "--lam 3000 (80,000 features at block 4096)")
    p.add_argument("--train", dest="train_path")
    p.add_argument("--test", dest="test_path")
    p.add_argument("--num-filters", type=int, default=256,
                   help="filters (upstream's documented run: 10000)")
    p.add_argument("--patch-size", type=int, default=6)
    p.add_argument("--patch-sample", type=int, default=10000,
                   help="patches the whitener is fitted on (upstream: 100000)")
    p.add_argument("--patch-norm", type=float, default=None,
                   help="normalise each patch, this under the root "
                   "(upstream: 10); off when not given")
    p.add_argument("--pool-size", type=int, default=13,
                   help="pooling window (upstream: 14)")
    p.add_argument("--pool-stride", type=int, default=13)
    p.add_argument("--alpha", type=float, default=0.25)
    p.add_argument("--lam", type=float, default=10.0,
                   help="ridge (upstream's documented run: 3000)")
    p.add_argument("--block-size", type=int, default=4096)
    p.add_argument("--num-iters", type=int, default=3,
                   help="solver epochs (upstream: 1)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--synthetic-n", type=int, default=2048)
    p.add_argument(
        "--feature-dtype", choices=["float32", "bfloat16"], default=None
    )
    a = p.parse_args(argv)
    conf = RandomPatchCifarConfig(
        train_path=a.train_path,
        test_path=a.test_path,
        num_filters=a.num_filters,
        patch_size=a.patch_size,
        patch_sample=a.patch_sample,
        patch_norm=a.patch_norm,
        pool_size=a.pool_size,
        pool_stride=a.pool_stride,
        alpha=a.alpha,
        lam=a.lam,
        block_size=a.block_size,
        num_iters=a.num_iters,
        seed=a.seed,
        synthetic_n=a.synthetic_n,
        feature_dtype=a.feature_dtype,  # Convolver normalizes "float32"→off
    )
    out = run(conf)
    print(out["summary"])
    print(f"total {out['seconds']:.2f}s")
    return out


if __name__ == "__main__":
    main()
