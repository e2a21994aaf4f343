"""TimitPipeline — the canonical speech pipeline.

Ref: src/main/scala/pipelines/speech/timit/TimitPipeline.scala
(BASELINE.json config: "MFCC + CosineRandomFeatures +
BlockLeastSquaresEstimator"): frame features → StandardScaler →
CosineRandomFeatures (Gaussian or Cauchy W, ~100k+ dims) → multi-epoch
BlockLeastSquaresEstimator → MaxClassifier (SURVEY.md §2.11) [unverified].

This is the first real stress of the distributed-linalg layer at high
feature dimension: the random-feature projection is one large MXU gemm and
the solve streams feature blocks through the psum-reduced BCD loop.

Upstream gathers ``numCosines`` cosine nodes of 4096 features each (50 by
default: 204,800 features) and solves at block 4096. Here they are one
projection of ``num_cosines * num_features`` columns drawn block by block
(``CosineRandomFeatures.create(blocks=...)``), so the scaler and the
projection are one program; the defaults stay what a laptop runs (one
block of 4096).
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

from keystone_tpu.evaluation import MulticlassClassifierEvaluator
from keystone_tpu.loaders.labeled_data import LabeledData
from keystone_tpu.loaders.timit import TimitFeaturesDataLoader
from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
from keystone_tpu.nodes.stats import CosineRandomFeatures, StandardScaler
from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
from keystone_tpu.utils.metrics import active_tracer, program_counters, span_of
from keystone_tpu.workflow import Pipeline


@dataclass
class TimitConfig:
    features_path: Optional[str] = None
    labels_path: Optional[str] = None
    test_features_path: Optional[str] = None
    test_labels_path: Optional[str] = None
    num_features: int = 4096  # features a cosine block
    num_cosines: int = 1  # cosine blocks (upstream's numCosines: 50)
    gamma: float = 0.055  # the RBF bandwidth scale of the reference setup
    distribution: str = "gaussian"  # or "cauchy"
    lam: float = 0.1
    block_size: int = 2048
    num_iters: int = 3
    num_phones: int = 24
    seed: int = 0
    synthetic_n: int = 4096


def build_featurizer(conf: TimitConfig, train_data) -> Pipeline:
    """Frames -> standard scaling -> ``num_cosines`` cosine blocks of
    ``num_features`` each, in block order. The scaler is fitted here, so
    the pipeline is two transformers that the optimizer fuses into one
    program."""
    scaler = StandardScaler().fit(train_data)
    return scaler.and_then(
        CosineRandomFeatures.create(
            input_dim=int(train_data.shape[1]),
            num_features=conf.num_cosines * conf.num_features,
            gamma=conf.gamma,
            distribution=conf.distribution,
            seed=conf.seed,
            blocks=conf.num_cosines,
        )
    )


def fit(conf: TimitConfig, train: LabeledData,
        num_phones: Optional[int] = None) -> Pipeline:
    """Fit on ``train``: the fitted pipeline, frames in and the phone
    index out (its stages: scaler, cosine features, block linear map,
    argmax). The one construction ``run`` (the CLI) and the benchmark
    share."""
    num_phones = conf.num_phones if num_phones is None else num_phones
    rows, dim_in = (int(s) for s in train.data.shape)
    tracer = active_tracer()
    # The root span of one whole fit. It closes when the solver's programs
    # are dispatched, not when the device has run them. It carries how the
    # fit's transformer programs were found (``program_counters``): a
    # closure call is a program traced for this fit alone.
    with span_of(tracer, "fit", "pipeline", pipeline="timit",
                 rows=rows) as root:
        calls = program_counters.calls()
        featurizer = build_featurizer(conf, train.data)
        # The features are computed once, here, and handed to the solver as
        # data. Nothing waits for them: the span covers the chain's
        # dispatch, and the device runs it while the host goes on.
        with span_of(tracer, "features.random", "pipeline", rows=rows,
                     dim_in=dim_in,
                     dim_out=conf.num_cosines * conf.num_features,
                     blocks=conf.num_cosines) as attrs:
            sent = program_counters.get("argument_bytes")
            features = featurizer(train.data).get()
            if attrs is not None:
                attrs["bytes"] = program_counters.get("argument_bytes") - sent
        targets = ClassLabelIndicators(num_phones)(train.labels)
        head = BlockLeastSquaresEstimator(
            block_size=conf.block_size,
            num_iters=conf.num_iters,
            lam=conf.lam,
        ).with_data(features, targets)
        fitted = featurizer.and_then(head).and_then(MaxClassifier()).fit()
        if root is not None:
            root.update(program_counters.since(calls))
        return fitted


def run(conf: TimitConfig) -> dict:
    if conf.features_path:
        if not conf.test_features_path:
            raise ValueError("test features are required with real data")
        train = TimitFeaturesDataLoader.load(conf.features_path, conf.labels_path)
        test = TimitFeaturesDataLoader.load(
            conf.test_features_path, conf.test_labels_path
        )
        num_phones = TimitFeaturesDataLoader.NUM_PHONES
    else:
        train, test = TimitFeaturesDataLoader.synthetic(
            n=conf.synthetic_n, num_phones=conf.num_phones, seed=conf.seed
        )
        num_phones = conf.num_phones

    t0 = time.perf_counter()
    pipeline = fit(conf, train, num_phones)
    predictions = pipeline(test.data).get()
    elapsed = time.perf_counter() - t0

    metrics = MulticlassClassifierEvaluator(num_phones).evaluate(
        predictions, test.labels
    )
    return {
        "test_accuracy": metrics.total_accuracy,
        "phone_error_rate": 1.0 - metrics.total_accuracy,
        "macro_f1": metrics.macro_f1,
        "seconds": elapsed,
        "summary": metrics.summary(),
    }


def main(argv=None):
    from keystone_tpu.utils.platform import setup_platform

    setup_platform()
    p = argparse.ArgumentParser(description="TIMIT speech pipeline")
    p.add_argument("--features", dest="features_path")
    p.add_argument("--labels", dest="labels_path")
    p.add_argument("--test-features", dest="test_features_path")
    p.add_argument("--test-labels", dest="test_labels_path")
    p.add_argument("--num-features", type=int, default=4096,
                   help="features a cosine block")
    p.add_argument("--num-cosines", type=int, default=1,
                   help="cosine blocks (upstream's numCosines: 50)")
    p.add_argument("--gamma", type=float, default=0.055)
    p.add_argument(
        "--distribution", choices=["gaussian", "cauchy"], default="gaussian"
    )
    p.add_argument("--lam", type=float, default=0.1)
    p.add_argument("--block-size", type=int, default=2048)
    p.add_argument("--num-iters", type=int, default=3)
    p.add_argument("--num-phones", type=int, default=24)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--synthetic-n", type=int, default=4096)
    a = p.parse_args(argv)
    out = run(
        TimitConfig(
            features_path=a.features_path,
            labels_path=a.labels_path,
            test_features_path=a.test_features_path,
            test_labels_path=a.test_labels_path,
            num_features=a.num_features,
            num_cosines=a.num_cosines,
            gamma=a.gamma,
            distribution=a.distribution,
            lam=a.lam,
            block_size=a.block_size,
            num_iters=a.num_iters,
            num_phones=a.num_phones,
            seed=a.seed,
            synthetic_n=a.synthetic_n,
        )
    )
    print(out["summary"])
    print(
        f"PER {out['phone_error_rate']:.4f} | total {out['seconds']:.2f}s"
    )
    return out


if __name__ == "__main__":
    main()
