"""RandomPatchCifarKernel — the CIFAR random-patch features under a Gaussian
kernel ridge head.

Ref: src/main/scala/pipelines/images/cifar/RandomPatchCifarKernel.scala
(BASELINE.json names ``KernelRidgeRegression`` among the solvers):
``RandomPatchCifar``'s featurizer (random patches → patch normalisation →
ZCA whitening → convolution with whitened random-patch filters → symmetric
rectification → spatial sum pooling) → StandardScaler →
KernelRidgeRegression(GaussianKernelGenerator(gamma), lambda, blockSize,
numEpochs) → MaxClassifier [unverified].

The benchmark's configuration ``cifar-random-patch-kernel`` carries the
sizes of Tu et al. (arXiv:1602.05310): 512 filters, window 14 at stride 13
(4,096 features), all 50,000 train rows, block 4096, three epochs. The
defaults below are a laptop's. Those sizes from the command line:

    bin/run-pipeline.sh RandomPatchCifarKernel --num-filters 512 \
        --patch-sample 100000 --patch-norm 10 --pool-size 14 --pool-stride 13 \
        --gamma 2e-4 --lam 1 --block-size 4096 --num-epochs 3

TPU notes: the featurizer is ``random_patch_cifar``'s, shared, not copied;
the head never takes the n x n kernel as an array: each n x b block is
generated from the features inside the solver's one program when it is
first visited, and kept there for the later epochs where the device's
memory has room for it (``nodes/learning/kernel_ridge.py``).
"""

from __future__ import annotations

import argparse
from dataclasses import dataclass

import jax.numpy as jnp

from keystone_tpu.nodes.learning import GaussianKernelGenerator, KernelRidgeRegression
from keystone_tpu.nodes.util import ClassLabelIndicators, MaxClassifier
from keystone_tpu.pipelines.images import random_patch_cifar
from keystone_tpu.pipelines.images.random_patch_cifar import (
    RandomPatchCifarConfig,
    fit_features,
)
from keystone_tpu.utils.metrics import active_tracer, program_counters, span_of
from keystone_tpu.workflow import Pipeline


@dataclass
class RandomPatchCifarKernelConfig(RandomPatchCifarConfig):
    """``RandomPatchCifarConfig``'s featurizer fields (its linear head's
    ``num_iters`` is not read), and the kernel head's."""

    num_filters: int = 64
    lam: float = 1.0
    block_size: int = 512
    gamma: float = 2e-4
    num_epochs: int = 3


def fit(conf: RandomPatchCifarKernelConfig, train_images, train_labels) -> Pipeline:
    """Fit on the train images: the fitted pipeline, images in and the
    class index out (its stages: convolver, rectifier, pooler, vectorizer,
    scaler, kernel block linear map, argmax). The one construction ``run``
    (the CLI) and the benchmark share."""
    train_images = jnp.asarray(train_images)
    # The root span of one whole fit, as ``random_patch_cifar.fit`` has it.
    with span_of(active_tracer(), "fit", "pipeline", pipeline="cifar-kernel",
                 rows=int(train_images.shape[0])) as root:
        calls = program_counters.calls()
        featurizer, scaler, scaled = fit_features(conf, train_images)
        targets = ClassLabelIndicators(conf.num_classes)(train_labels)
        head = KernelRidgeRegression(
            GaussianKernelGenerator(conf.gamma),
            lam=conf.lam,
            block_size=conf.block_size,
            num_epochs=conf.num_epochs,
        ).with_data(scaled, targets)
        del scaled
        fitted = (featurizer.and_then(scaler).and_then(head)
                  .and_then(MaxClassifier()).fit())
        if root is not None:
            root.update(program_counters.since(calls))
        return fitted


def run(conf: RandomPatchCifarKernelConfig) -> dict:
    return random_patch_cifar.run(conf, fit)


def main(argv=None):
    from keystone_tpu.utils.platform import setup_platform

    setup_platform()
    p = argparse.ArgumentParser(
        description="RandomPatchCifarKernel pipeline. The defaults are a "
        "laptop's; the benchmark's sizes are --num-filters 512 --patch-sample "
        "100000 --patch-norm 10 --pool-size 14 --pool-stride 13 --gamma 2e-4 "
        "--lam 1 --block-size 4096 --num-epochs 3 (4,096 features)")
    p.add_argument("--train", dest="train_path")
    p.add_argument("--test", dest="test_path")
    p.add_argument("--num-filters", type=int, default=64)
    p.add_argument("--patch-size", type=int, default=6)
    p.add_argument("--patch-sample", type=int, default=10000)
    p.add_argument("--patch-norm", type=float, default=None,
                   help="normalise each patch, this under the root "
                   "(upstream: 10); off when not given")
    p.add_argument("--pool-size", type=int, default=13)
    p.add_argument("--pool-stride", type=int, default=13)
    p.add_argument("--alpha", type=float, default=0.25)
    p.add_argument("--gamma", type=float, default=2e-4,
                   help="the Gaussian kernel's exp(-gamma |x - z|^2)")
    p.add_argument("--lam", type=float, default=1.0, help="ridge")
    p.add_argument("--block-size", type=int, default=512,
                   help="training rows a kernel block (the benchmark: 4096)")
    p.add_argument("--num-epochs", type=int, default=3,
                   help="sweeps of block Gauss-Seidel over the blocks")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--synthetic-n", type=int, default=2048)
    a = p.parse_args(argv)
    out = run(RandomPatchCifarKernelConfig(**vars(a)))
    print(out["summary"])
    print(f"total {out['seconds']:.2f}s")
    return out


if __name__ == "__main__":
    main()
