"""MnistRandomFFT — the canonical MNIST pipeline.

Ref: src/main/scala/pipelines/images/mnist/MnistRandomFFT.scala
(BASELINE.json config: "random-Fourier features + LinearMapEstimator"):
for each of `num_ffts` branches, RandomSignNode → PaddedFFT →
LinearRectifier; branches merged with Pipeline.gather; a linear head on the
gathered features; MaxClassifier [unverified]. Upstream launches 200 FFTs
(102,400 features for MNIST's 784 pixels) under
BlockLeastSquaresEstimator(blockSize 2048, numIter 1, lambda 0); the
benchmark's cell runs it for 3 epochs, at a lambda of 1e-4 where a feature
the rectifier leaves at 0 in every row would make a block's gram singular:

    bin/run-pipeline.sh MnistRandomFFT --num-ffts 200 --block-size 2048 \\
        --lam 1e-4 --num-epochs 3

The head is chosen from the feature width: a width of at most one block is
solved densely (``LinearMapEstimator``, the normal equations of d columns);
a wider one by the block solver. There the gathered branches, one chain with
different signs, are lowered by the optimizer to one stacked featurizer
whose blocks the solver makes from the images inside its loop
(``workflow.rules.GeneratedBlocksRule``): at 200 FFTs and 60,000 images the
24.6 GB feature matrix never exists, and the fitted map makes each block of
the rows it is applied to again.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.loaders import MnistLoader
from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator, LinearMapEstimator
from keystone_tpu.nodes.stats import LinearRectifier, PaddedFFT, RandomSignNode
from keystone_tpu.nodes.stats.fft import _next_pow2
from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
from keystone_tpu.utils.metrics import active_tracer, program_counters, span_of
from keystone_tpu.workflow import Pipeline


@dataclass
class MnistRandomFFTConfig:
    train_path: Optional[str] = None
    test_path: Optional[str] = None
    num_ffts: int = 4
    block_size: int = 2048
    num_epochs: int = 1
    # The features are unscaled (upstream's PaddedFFT): a gram diagonal of
    # about 800 a row at pixels in [0, 1]. 1000 keeps the synthetic fits of
    # 1,024 rows at 2,048 features inside tools/acceptance.py's band, with
    # and without its label noise (100 read 0.74 against a floor of 0.85).
    lam: float = 1000.0
    seed: int = 0
    num_classes: int = 10
    synthetic_n: int = 4096  # used when no data paths are given


def build_pipeline(conf: MnistRandomFFTConfig, train, train_labels) -> Pipeline:
    dim = train.shape[1]
    branches = [
        RandomSignNode.create(dim, seed=conf.seed + i)
        .and_then(PaddedFFT())
        .and_then(LinearRectifier())
        for i in range(conf.num_ffts)
    ]
    features = Pipeline.gather(branches)
    targets = ClassLabelIndicators(conf.num_classes)(train_labels)
    # A branch keeps the real parts of half its padded transform.
    if conf.num_ffts * (_next_pow2(dim) // 2) <= conf.block_size:
        head = LinearMapEstimator(lam=conf.lam)
    else:
        head = BlockLeastSquaresEstimator(
            conf.block_size, num_iters=conf.num_epochs, lam=conf.lam)
    return features.and_then(head, train, targets).and_then(MaxClassifier())


def fit(conf: MnistRandomFFTConfig, train, train_labels) -> Pipeline:
    """The fitted pipeline, images in and the class index out: the one
    construction the CLI and the benchmark share, under the root span of a
    whole fit."""
    with span_of(active_tracer(), "fit", "pipeline", pipeline="mnist-fft",
                 rows=int(np.shape(train)[0])) as root:
        calls = program_counters.calls()
        fitted = build_pipeline(conf, train, train_labels).fit()
        if root is not None:
            root.update(program_counters.since(calls))
        return fitted


def run(conf: MnistRandomFFTConfig) -> dict:
    t0 = time.perf_counter()
    if conf.train_path:
        if not conf.test_path:
            raise ValueError(
                "--test is required when --train is given (evaluating on the "
                "training set would report memorization as test accuracy)"
            )
        train = MnistLoader.load(conf.train_path)
        test = MnistLoader.load(conf.test_path)
    else:
        train, test = MnistLoader.synthetic(n=conf.synthetic_n, seed=conf.seed)
    t_load = time.perf_counter() - t0

    t0 = time.perf_counter()
    pipeline = fit(conf, train.data, train.labels)
    predictions = pipeline(test.data).get()
    t_fit = time.perf_counter() - t0

    metrics = MulticlassClassifierEvaluator(conf.num_classes).evaluate(
        predictions, test.labels
    )
    train_pred = pipeline(train.data).get()
    train_metrics = MulticlassClassifierEvaluator(conf.num_classes).evaluate(
        train_pred, train.labels
    )
    return {
        "test_accuracy": metrics.total_accuracy,
        "train_accuracy": train_metrics.total_accuracy,
        "macro_f1": metrics.macro_f1,
        "load_seconds": t_load,
        "fit_predict_seconds": t_fit,
        "summary": metrics.summary(),
    }


def main(argv=None):
    from keystone_tpu.utils.platform import setup_platform

    setup_platform()
    p = argparse.ArgumentParser(
        description="MnistRandomFFT pipeline. Upstream's launch: --num-ffts "
        "200 --block-size 2048 (102,400 features); the benchmark adds --lam "
        "1e-4 --num-epochs 3")
    p.add_argument("--train", dest="train_path")
    p.add_argument("--test", dest="test_path")
    p.add_argument("--num-ffts", type=int, default=4)
    p.add_argument("--block-size", type=int, default=2048,
                   help="columns a block of the block solver, used where the "
                   "features are wider than one block")
    p.add_argument("--num-epochs", type=int, default=1,
                   help="sweeps of the block solver over the blocks")
    p.add_argument("--lam", type=float, default=1000.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--synthetic-n", type=int, default=4096)
    a = p.parse_args(argv)
    out = run(MnistRandomFFTConfig(**vars(a)))
    print(out["summary"])
    print(
        f"train acc {out['train_accuracy']:.4f} | "
        f"load {out['load_seconds']:.2f}s | fit+predict {out['fit_predict_seconds']:.2f}s"
    )
    return out


if __name__ == "__main__":
    main()
