"""ImageNetSiftLcsFV — the north-star pipeline.

Ref: src/main/scala/pipelines/images/imagenet/ImageNetSiftLcsFV.scala
(BASELINE.json config: "SIFT/LCS + GMM FisherVector +
BlockWeightedLeastSquares (64k-dim)"; SURVEY.md §2.11, §3.4) [unverified]:
two descriptor branches — grayscale dense SIFT and local color statistics
— each PCA-reduced, Fisher-vector encoded against its own GMM, signed-sqrt
and L2 normalized; branches concatenated (Pipeline.gather); class-balanced
block weighted least squares; top-5 error via TopKClassifier.

TPU notes: each branch's PCA→FV→normalize tail fuses into one XLA
computation; the gathered 2·(2·k·pca_dims)-dim features feed the
psum-reduced weighted BCD solver.

Full-scale config (the REAL-DATA default via resolve_scale, matching
BASELINE.json "64k-dim"):

    pca_dims=64  gmm_k=256  → feature_dim = 2·(2·256·64) = 65,536
    solver: weighted BCD, block_size=auto (HBM-safe, 8192 cap), 3 epochs

Synthetic/CI runs default to gmm_k=16 (4,096-dim) so smoke tests stay
fast; pass --gmm-k 256 to force the headline scale anywhere.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from keystone_tpu.loaders.imagenet import ImageNetLoader, LabeledData
from keystone_tpu.nodes.images import GrayScaler
from keystone_tpu.nodes.images.external import SIFTExtractor
from keystone_tpu.nodes.images.external.fisher_vector import (
    fit_fisher_featurizer,
)
from keystone_tpu.nodes.images.lcs import LCSExtractor
from keystone_tpu.nodes.learning import BlockWeightedLeastSquaresEstimator
from keystone_tpu.nodes.util import ClassLabelIndicators, TopKClassifier
from keystone_tpu.utils.mesh import num_data_shards
from keystone_tpu.utils.metrics import active_tracer, program_counters, span_of
from keystone_tpu.workflow import Pipeline, placed_batch


def _scoring_engine(model, stream_batch: int):
    """The classifier head as a replica-pool serving engine for the
    streamed scorer's data-parallel offline apply, or None when the model
    can't take the AOT path (not jittable / row-coupled) — the caller
    falls back to ``batch_call``. A single bucket at the stream batch
    size keeps warmup to one compile per device: the stream only ever
    yields full batches plus one trailing partial (padded up)."""
    from keystone_tpu.workflow.serving import (
        CompiledPipeline,
        RowDependenceError,
    )

    try:
        # Stable name = explicit aggregation key: repeated scoring passes
        # in one process reuse the same registry entries instead of
        # leaking a fresh serve.dispatch[cpN]/gauge set per pass.
        return CompiledPipeline(
            model, buckets=(stream_batch,), name="imagenet-score-head"
        )
    except (TypeError, RowDependenceError):
        return None


@dataclass
class ImageNetSiftLcsFVConfig:
    data_path: Optional[str] = None
    test_data_path: Optional[str] = None
    label_map_path: Optional[str] = None
    sift_step: int = 4
    sift_bin: int = 4
    sift_backend: str = "xla"
    lcs_step: int = 4
    lcs_bin: int = 4
    pca_dims: int = 64
    # None = resolve by data source (resolve_scale): REAL data gets the
    # reference headline config — gmm_k=256 → 2·(2·256·64) = 65,536-dim
    # gathered features (BASELINE.json "64k-dim"), 3 solver epochs — while
    # the synthetic/CI path keeps gmm_k=16 (4,096-dim) so smoke runs stay
    # minutes, not hours. An explicit value always wins.
    gmm_k: Optional[int] = None
    gmm_iters: int = 20
    descriptor_sample: int = 200_000
    lam: float = 1e-3
    mixture_weight: float = 0.5
    block_size: "int | str" = "auto"  # resolve_block_size: HBM-safe, 8192 cap
    num_iters: Optional[int] = None
    top_k: int = 5
    # Test-time augmentation: score center+corner crops (flipped too) per
    # image and average (Ref: AugmentedExamplesEvaluator, SURVEY.md §2.10).
    augment: bool = False
    augment_crop: int = 0  # 0 = 7/8 of the image side
    fv_backend: str = "tpu"
    seed: int = 0
    synthetic_n: int = 512
    synthetic_classes: int = 16
    # Out-of-core mode: fit the featurizer on a bounded image sample, then
    # stream images from disk (decode-ahead) and featurize batch by batch —
    # only FEATURES are held on host, and the solve streams feature blocks
    # to the device. The single-host projection of the reference's
    # cache-features-not-images cluster layout (SURVEY.md §7 hard parts 1+4).
    stream: bool = False
    stream_batch: int = 256
    fit_sample_images: int = 512
    # Checkpoint directory for the chunked/streamed solve: the BCD solver
    # snapshots per-chunk accumulator state there and resumes after a
    # crash (including one mid-way through the donated chunk loop — the
    # chaos harness pins that path). None = no checkpointing.
    checkpoint_dir: Optional[str] = None


def resolve_scale(conf: ImageNetSiftLcsFVConfig) -> ImageNetSiftLcsFVConfig:
    """Fill gmm_k/num_iters by data source: the real-data path defaults to
    the reference's full-scale config (64k-dim features, 3 epochs), the
    synthetic path to CI scale. Called once at the top of run()."""
    from dataclasses import replace

    real = conf.data_path is not None
    return replace(
        conf,
        gmm_k=conf.gmm_k if conf.gmm_k is not None else (256 if real else 16),
        num_iters=(
            conf.num_iters if conf.num_iters is not None else (3 if real else 2)
        ),
    )


def build_featurizer(conf: ImageNetSiftLcsFVConfig, train_images) -> Pipeline:
    sift_front = GrayScaler().and_then(
        SIFTExtractor(step=conf.sift_step, bin_size=conf.sift_bin,
                      backend=conf.sift_backend)
    )
    lcs_front = LCSExtractor(step=conf.lcs_step, bin_size=conf.lcs_bin).to_pipeline()
    branches = [
        fit_fisher_featurizer(
            front,
            train_images,
            pca_dims=conf.pca_dims,
            gmm_k=conf.gmm_k,
            em_iters=conf.gmm_iters,
            sample_size=conf.descriptor_sample,
            backend=conf.fv_backend,
            seed=seed,
        )
        for front, seed in ((sift_front, conf.seed), (lcs_front, conf.seed + 1))
    ]
    return Pipeline.gather(branches)


def _build_tta(conf: ImageNetSiftLcsFVConfig, side: int):
    """Patcher + score averager for the reference's TTA protocol (center +
    four corners, each flipped = 10 views; crop defaults to 7/8 of the
    image side). Shared by the eager and streamed paths so the crop
    protocol can't drift between them."""
    from keystone_tpu.evaluation.augmented import AugmentedExamplesEvaluator
    from keystone_tpu.nodes.images import CenterCornerPatcher

    crop = conf.augment_crop or (side * 7) // 8
    patcher = CenterCornerPatcher(crop_size=crop, with_flips=True)
    return patcher, AugmentedExamplesEvaluator(patcher.num_views)


def run_streamed(conf: ImageNetSiftLcsFVConfig) -> dict:
    """Out-of-core execution of the north-star pipeline.

    Images never sit in memory all at once: the featurizer (PCA/GMM) fits
    on ``fit_sample_images``, train batches stream through it (decode of
    batch b+1 overlapping featurization of batch b on real paths), the
    accumulated FEATURE matrix — ~3× smaller than the images at the
    64k-dim config — feeds the host-streamed weighted BCD, and test
    batches stream through scoring the same way.

    With ``augment`` (the reference's AugmentedExamplesEvaluator protocol,
    SURVEY.md §2.10), each test batch expands to its center+corner crop
    views; views are featurized and scored in ``stream_batch``-sized
    slices so device batches stay bounded, and only the (views, classes)
    score rows are held before per-image averaging — the feature matrix
    for the views is never materialized whole.
    """
    if conf.data_path:
        if not (conf.test_data_path and conf.label_map_path):
            raise ValueError("real data requires test path and label map")
        label_map = ImageNetLoader.load_label_map(conf.label_map_path)
        # Class-balanced fitting sample: PCA/GMM fit on a few images from
        # EVERY synset — a prefix of the sorted walk would be one class.
        fit_sample = ImageNetLoader.load_balanced_sample(
            conf.data_path, label_map, total=conf.fit_sample_images
        )
        num_classes = max(label_map.values()) + 1

        def train_batches():
            return ImageNetLoader.stream_batches(
                conf.data_path, label_map, batch_size=conf.stream_batch
            )

        def test_batches():
            return ImageNetLoader.stream_batches(
                conf.test_data_path, label_map, batch_size=conf.stream_batch
            )

    else:
        from keystone_tpu.loaders.stream import BatchIterator

        train, test = ImageNetLoader.synthetic(
            n=conf.synthetic_n, num_classes=conf.synthetic_classes
        )
        fit_sample = train.data[: conf.fit_sample_images]
        num_classes = conf.synthetic_classes

        def train_batches():
            return iter(
                BatchIterator.from_arrays(
                    train.data, train.labels, conf.stream_batch
                )
            )

        def test_batches():
            return iter(
                BatchIterator.from_arrays(
                    test.data, test.labels, conf.stream_batch
                )
            )

    t0 = time.perf_counter()
    featurizer = build_featurizer(conf, fit_sample)

    # apply_batches runs the batch producer (JPEG decode / synthetic read)
    # on a prefetch thread while the fused featurizer chain computes on the
    # current batch — decode of batch b+1 overlaps featurization of b on
    # every source, not just the real-data loader's decode-ahead pool.
    feats, labels = [], []
    for F, y in featurizer.apply_batches(train_batches()):
        feats.append(np.asarray(F))
        labels.append(np.asarray(y))
    if not feats:
        raise ValueError(
            "the training stream produced no batches — check that the data "
            "directory's synsets appear in the label map"
        )
    # Assemble in place, freeing each chunk as it lands: peak host memory is
    # the feature matrix + ONE batch, not the 2× a concatenate would cost
    # (the whole point of this mode at the 64k-dim scale).
    n_total = sum(len(f) for f in feats)
    A_host = np.empty((n_total, feats[0].shape[1]), dtype=feats[0].dtype)
    off = 0
    while feats:
        f = feats.pop(0)
        A_host[off : off + len(f)] = f
        off += len(f)
    y_train = np.concatenate(labels)

    targets = np.asarray(ClassLabelIndicators(num_classes)(y_train))
    solver = BlockWeightedLeastSquaresEstimator(
        block_size=conf.block_size,
        num_iters=conf.num_iters,
        lam=conf.lam,
        mixture_weight=conf.mixture_weight,
        checkpoint_dir=conf.checkpoint_dir,
        stream=True,  # feature blocks stream to the device, double-buffered
    )
    model = solver.fit(A_host, targets)
    del A_host

    patcher = averager = None
    if conf.augment:
        patcher, averager = _build_tta(conf, int(np.asarray(fit_sample).shape[1]))

    def score_batches():
        """(scores, labels) per test batch, ingest-overlapped either way:
        the plain path featurizes via apply_batches (decode on the prefetch
        thread), the TTA path prefetches raw batches and expands views on
        the consumer side (the view tensor must stay sub-batch-bounded)."""
        if patcher is None:
            head = _scoring_engine(model, conf.stream_batch)
            feats = featurizer.apply_batches(test_batches())
            if head is not None:
                # Data-parallel offline scoring: the classifier head runs
                # from its replica pool (one AOT ladder per local device),
                # round-robining featurized batches so up to
                # inflight x replicas device calls overlap the prefetch
                # thread's decode/featurize. prefetch_depth=0: the source
                # generator already prefetches; the async window supplies
                # the overlap here.
                yield from head.apply_batches(feats, prefetch_depth=0)
                return
            for F, y in feats:
                # batch_call (not apply_batch) so the classifier head runs
                # jitted and, under KEYSTONE_SERVE_BUCKETS, shape-stable:
                # the stream's trailing partial batch otherwise recompiles
                # the whole blocked-gemm chain for its one-off row count.
                yield model.batch_call(np.asarray(F)), y
            return
        from keystone_tpu.loaders.stream import prefetched

        with prefetched(iter(test_batches())) as src:
            for X, y in src:
                # Patch per image sub-batch so the view tensor never
                # exceeds ~stream_batch rows on the device (a whole-batch
                # patch at the real-data scale is a ~2 GB transient, 10×
                # the working set this mode exists to bound).
                X = np.asarray(X)
                sub = max(1, conf.stream_batch // patcher.num_views)
                view_scores = np.concatenate([
                    np.asarray(model.batch_call(np.asarray(
                        featurizer(patcher(X[i : i + sub])).get()
                    )))
                    for i in range(0, len(X), sub)
                ])
                yield averager.average_scores(view_scores), y

    correct = []
    top1_wrong = []
    for scores, y in score_batches():
        topk = np.asarray(TopKClassifier(conf.top_k)(scores))
        correct.append((topk == np.asarray(y)[:, None]).any(axis=1))
        top1_wrong.append(topk[:, 0] != np.asarray(y))
    correct = np.concatenate(correct)
    top1_wrong = np.concatenate(top1_wrong)
    elapsed = time.perf_counter() - t0

    top_k_error = float(1.0 - correct.mean())
    top1 = float(top1_wrong.mean())
    return {
        "top_k_error": top_k_error,
        "top_1_error": top1,
        "feature_dim": 2 * (2 * conf.gmm_k * conf.pca_dims),
        "seconds": elapsed,
        "summary": (
            f"top-{conf.top_k} error: {top_k_error:.4f} | "
            f"top-1 error: {top1:.4f} (streamed"
            + (f", TTA x{patcher.num_views})" if patcher else ")")
        ),
        **({"num_views": patcher.num_views} if patcher else {}),
    }


def load_data(conf: ImageNetSiftLcsFVConfig):
    """(train, test, num_classes) for the in-memory path: the real data
    when ``conf.data_path`` is set, the seeded synthetic set otherwise."""
    if conf.data_path:
        if not (conf.test_data_path and conf.label_map_path):
            raise ValueError("real data requires test path and label map")
        label_map = ImageNetLoader.load_label_map(conf.label_map_path)
        train = ImageNetLoader.load(conf.data_path, label_map)
        test = ImageNetLoader.load(conf.test_data_path, label_map)
        return train, test, int(max(train.labels.max(), test.labels.max())) + 1
    train, test = ImageNetLoader.synthetic(
        n=conf.synthetic_n, num_classes=conf.synthetic_classes
    )
    return train, test, conf.synthetic_classes


def fit(conf: ImageNetSiftLcsFVConfig, train: LabeledData, num_classes: int):
    """Fit the in-memory pipeline on ``train``: returns ``(featurizer,
    scored)``, the fitted two-branch featurizer (image → gathered Fisher
    vectors) and the fitted pipeline (image → class scores). The one
    construction shared by ``run`` (the CLI) and ``chip_smoke.py``;
    ``conf`` must already be through ``resolve_scale``."""
    # The root span of one whole fit: both branches' eager fits
    # (build_featurizer) and the graph's, so every span below shares its
    # root_id. It closes when the solver's programs are dispatched, not
    # when the device has run them. It carries how the fit's transformer
    # programs were found (``program_counters``): a closure call is a
    # program traced for this fit alone; and over how many devices of the
    # mesh its rows lie, with what its reductions across them were handed
    # (``collective_bytes``).
    shards = num_data_shards()
    with span_of(active_tracer(), "fit", "pipeline",
                 pipeline="ImageNetSiftLcsFV",
                 rows=int(len(train.data)), shards=shards,
                 rows_per_shard=-(-int(len(train.data)) // shards)) as attrs:
        calls = program_counters.calls()
        # Four walks read the train images (each branch's descriptors,
        # then each branch's whole chain): they reach the device once.
        with placed_batch(train.data) as images:
            featurizer = build_featurizer(conf, images)
            # A node of the fit's graph, not an array made here: the walk
            # then signs the labels (a few KB), where a (rows, classes)
            # device array would come back to the host to be hashed whole.
            targets = ClassLabelIndicators(num_classes).apply_pipeline(
                train.labels
            )
            solver = BlockWeightedLeastSquaresEstimator(
                block_size=conf.block_size,
                num_iters=conf.num_iters,
                lam=conf.lam,
                mixture_weight=conf.mixture_weight,
                checkpoint_dir=conf.checkpoint_dir,
            )
            fitted = featurizer.and_then(solver, images, targets).fit()
        if attrs is not None:
            attrs.update(program_counters.since(calls))
    return featurizer, fitted


def top_k_pipeline(conf: ImageNetSiftLcsFVConfig, scored: Pipeline) -> Pipeline:
    """Image in, top-k class indices out: what evaluation scores and what a
    daemon serves."""
    return scored.and_then(TopKClassifier(conf.top_k))


def run(conf: ImageNetSiftLcsFVConfig) -> dict:
    conf = resolve_scale(conf)
    if conf.stream:
        return run_streamed(conf)
    train, test, num_classes = load_data(conf)

    t0 = time.perf_counter()
    _featurizer, scored = fit(conf, train, num_classes)
    if conf.augment:
        patcher, averager = _build_tta(conf, test.data.shape[1])
        view_scores = np.asarray(scored(patcher(test.data)).get())
        avg = averager.average_scores(view_scores)
        topk = np.asarray(TopKClassifier(conf.top_k)(avg))
    else:
        pipeline = top_k_pipeline(conf, scored)
        topk = np.asarray(pipeline(test.data).get())  # (n, top_k)
    elapsed = time.perf_counter() - t0

    correct = (topk == test.labels[:, None]).any(axis=1)
    top_k_error = float(1.0 - correct.mean())
    top1 = float((topk[:, 0] != test.labels).mean())
    return {
        "top_k_error": top_k_error,
        "top_1_error": top1,
        "feature_dim": 2 * (2 * conf.gmm_k * conf.pca_dims),
        "seconds": elapsed,
        "summary": (
            f"top-{conf.top_k} error: {top_k_error:.4f} | "
            f"top-1 error: {top1:.4f}"
        ),
    }


def main(argv=None):
    from keystone_tpu.utils.platform import setup_platform

    setup_platform()
    p = argparse.ArgumentParser(description="ImageNet SIFT+LCS+FV pipeline")
    p.add_argument("--data", dest="data_path")
    p.add_argument("--test-data", dest="test_data_path")
    p.add_argument("--label-map", dest="label_map_path")
    p.add_argument("--pca-dims", type=int, default=64)
    p.add_argument("--gmm-k", type=int, default=None,
                   help="GMM components per branch (default: 256 with real "
                   "data = the reference's 64k-dim config; 16 synthetic)")
    p.add_argument("--lam", type=float, default=1e-3)
    p.add_argument("--mixture-weight", type=float, default=0.5)
    p.add_argument("--top-k", type=int, default=5)
    p.add_argument("--augment", action="store_true",
                   help="test-time augmentation over center+corner crops")
    p.add_argument("--augment-crop", type=int, default=0,
                   help="crop side in pixels (0 = 7/8 of the image side)")
    p.add_argument("--fv-backend", choices=["tpu", "pallas", "native"], default="tpu")
    p.add_argument("--sift-backend", choices=["native", "xla"], default="xla",
                   help="xla runs dense SIFT on the device (host keeps only "
                   "decode); native is the C++ kernel on the host")
    p.add_argument("--stream", action="store_true",
                   help="out-of-core: stream images, hold only features")
    p.add_argument("--stream-batch", type=int, default=256)
    p.add_argument("--fit-sample-images", type=int, default=512)
    p.add_argument("--checkpoint-dir", default=None,
                   help="snapshot/resume dir for the chunked solve")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--synthetic-n", type=int, default=512)
    p.add_argument("--synthetic-classes", type=int, default=16)
    a = p.parse_args(argv)
    out = run(
        ImageNetSiftLcsFVConfig(
            data_path=a.data_path,
            test_data_path=a.test_data_path,
            label_map_path=a.label_map_path,
            pca_dims=a.pca_dims,
            gmm_k=a.gmm_k,
            lam=a.lam,
            mixture_weight=a.mixture_weight,
            top_k=a.top_k,
            augment=a.augment,
            augment_crop=a.augment_crop,
            fv_backend=a.fv_backend,
            sift_backend=a.sift_backend,
            stream=a.stream,
            stream_batch=a.stream_batch,
            fit_sample_images=a.fit_sample_images,
            checkpoint_dir=a.checkpoint_dir,
            seed=a.seed,
            synthetic_n=a.synthetic_n,
            synthetic_classes=a.synthetic_classes,
        )
    )
    print(out["summary"])
    print(f"feature dim {out['feature_dim']} | total {out['seconds']:.2f}s")
    return out


if __name__ == "__main__":
    main()
