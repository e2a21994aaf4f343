"""VOCSIFTFisher — SIFT + Fisher-vector VOC multi-label pipeline.

Ref: src/main/scala/pipelines/images/voc/VOCSIFTFisher.scala
(SURVEY.md §2.11, §3.4) [unverified]: grayscale → dense SIFT →
PCA (fit on a descriptor sample) → GMM (EM) → FisherVector →
SignedHellingerMapper → L2 normalize → block least squares → mAP.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from keystone_tpu.evaluation.mean_average_precision import (
    MeanAveragePrecisionEvaluator,
)
from keystone_tpu.loaders.voc import VOCLoader
from keystone_tpu.nodes.images import GrayScaler
from keystone_tpu.nodes.images.external import SIFTExtractor
from keystone_tpu.nodes.images.external.fisher_vector import (
    fit_fisher_featurizer,
)
from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
from keystone_tpu.workflow import Pipeline, placed_batch


@dataclass
class VOCSIFTFisherConfig:
    image_dir: Optional[str] = None
    annotation_dir: Optional[str] = None
    test_image_dir: Optional[str] = None
    test_annotation_dir: Optional[str] = None
    sift_step: int = 4
    sift_bin: int = 4
    sift_backend: str = "xla"
    pca_dims: int = 64
    gmm_k: int = 16
    gmm_iters: int = 20
    descriptor_sample: int = 100_000
    lam: float = 1e-3
    block_size: int = 4096
    num_iters: int = 2
    fv_backend: str = "tpu"
    seed: int = 0
    synthetic_n: int = 192
    synthetic_classes: int = 6


def build_featurizer(conf: VOCSIFTFisherConfig, train_images) -> Pipeline:
    """Fit PCA + GMM on training descriptors; return the full featurizer."""
    front = GrayScaler().and_then(
        SIFTExtractor(step=conf.sift_step, bin_size=conf.sift_bin,
                      backend=conf.sift_backend)
    )
    return fit_fisher_featurizer(
        front,
        train_images,
        pca_dims=conf.pca_dims,
        gmm_k=conf.gmm_k,
        em_iters=conf.gmm_iters,
        sample_size=conf.descriptor_sample,
        backend=conf.fv_backend,
        seed=conf.seed,
    )


def run(conf: VOCSIFTFisherConfig) -> dict:
    if conf.image_dir:
        if not (
            conf.annotation_dir
            and conf.test_image_dir
            and conf.test_annotation_dir
        ):
            raise ValueError(
                "real data requires train+test image and annotation dirs"
            )
        train = VOCLoader.load(conf.image_dir, conf.annotation_dir)
        test = VOCLoader.load(conf.test_image_dir, conf.test_annotation_dir)
        num_classes = train.labels.shape[1]
    else:
        train, test = VOCLoader.synthetic(
            n=conf.synthetic_n, num_classes=conf.synthetic_classes
        )
        num_classes = conf.synthetic_classes

    t0 = time.perf_counter()
    # Two walks read the train images (the branch's descriptors, then its
    # whole chain): they reach the device once.
    with placed_batch(train.data) as images:
        featurizer = build_featurizer(conf, images)
        targets = (2.0 * train.labels - 1.0).astype(np.float32)
        pipeline = featurizer.and_then(
            BlockLeastSquaresEstimator(
                block_size=conf.block_size, num_iters=conf.num_iters, lam=conf.lam
            ),
            images,
            targets,
        )
        scores = np.asarray(pipeline(test.data).get())
    elapsed = time.perf_counter() - t0

    result = MeanAveragePrecisionEvaluator(num_classes).evaluate(
        scores, test.labels
    )
    return {
        "map": result["map"],
        "per_class_ap": result["per_class_ap"].tolist(),
        "seconds": elapsed,
        "summary": f"mAP: {result['map']:.4f}",
    }


def main(argv=None):
    from keystone_tpu.utils.platform import setup_platform

    setup_platform()
    p = argparse.ArgumentParser(description="VOC SIFT+FisherVector pipeline")
    p.add_argument("--images", dest="image_dir")
    p.add_argument("--annotations", dest="annotation_dir")
    p.add_argument("--test-images", dest="test_image_dir")
    p.add_argument("--test-annotations", dest="test_annotation_dir")
    p.add_argument("--pca-dims", type=int, default=64)
    p.add_argument("--gmm-k", type=int, default=16)
    p.add_argument("--lam", type=float, default=1e-3)
    p.add_argument("--fv-backend", choices=["tpu", "pallas", "native"], default="tpu")
    p.add_argument("--sift-backend", choices=["native", "xla"], default="xla",
                   help="xla runs dense SIFT on the device (host keeps only "
                   "decode); native is the C++ kernel on the host")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--synthetic-n", type=int, default=192)
    a = p.parse_args(argv)
    out = run(
        VOCSIFTFisherConfig(
            image_dir=a.image_dir,
            annotation_dir=a.annotation_dir,
            test_image_dir=a.test_image_dir,
            test_annotation_dir=a.test_annotation_dir,
            pca_dims=a.pca_dims,
            gmm_k=a.gmm_k,
            lam=a.lam,
            fv_backend=a.fv_backend,
            sift_backend=a.sift_backend,
            seed=a.seed,
            synthetic_n=a.synthetic_n,
        )
    )
    print(out["summary"])
    print(f"total {out['seconds']:.2f}s")
    return out


if __name__ == "__main__":
    main()
