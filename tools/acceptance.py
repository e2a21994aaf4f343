"""Quality-floor acceptance harness (VERDICT r2 #5; SURVEY.md §7 stage-2
acceptance).

    python tools/acceptance.py <data-root> [--pipelines NAME ...]
    python tools/acceptance.py --synthetic [--pipelines NAME ...]

Runs every canonical pipeline against real datasets under <data-root> and
asserts the BASELINE.md floors, printing ONE pass/fail table and exiting
non-zero on any failure — so the first data-available session is a run,
not a porting exercise. `--synthetic` runs the deterministic generated
datasets with the CI floors instead (the same floors the test suite pins),
validating the harness itself in the no-network environment (synthetic
configs are the CI-scale ones the tests pin — full defaults are sized
for real data).

Expected <data-root> layout (every piece optional — missing data SKIPs):

    mnist/train.csv mnist/test.csv        (label-first CSV; or IDX pairs
                                           mnist/train-*, mnist/t10k-*)
    cifar/train.bin cifar/test.bin        (CIFAR-10 binary records)
    newsgroups/train/<group>/<doc>        (directory-per-class)
    newsgroups/test/<group>/<doc>
    amazon/train.jsonl amazon/test.jsonl  ({"reviewText", "overall"})
    timit/train.npz timit/test.npz        (features + labels arrays)
    voc/JPEGImages voc/Annotations        (train) + voc/Test{JPEGImages,
                                           Annotations}
    imagenet/train/<synset>.tar|/         + imagenet/val/... +
    imagenet/labels.txt                   (synset -> int label map)

Floors marked (provisional) come from BASELINE.md's low-confidence
reconstructed rows and must be re-derived when the reference mounts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _mnist(root):
    from keystone_tpu.pipelines.images import mnist_random_fft as m

    if root is None:
        return m.run(m.MnistRandomFFTConfig(num_ffts=2, synthetic_n=1024))
    base = os.path.join(root, "mnist")
    csv_tr, csv_te = os.path.join(base, "train.csv"), os.path.join(base, "test.csv")
    if os.path.exists(csv_tr):
        tr, te = csv_tr, csv_te
    elif os.path.exists(os.path.join(base, "train-images-idx3-ubyte")):
        tr, te = os.path.join(base, "train"), os.path.join(base, "t10k")
    else:
        return None
    return m.run(m.MnistRandomFFTConfig(train_path=tr, test_path=te))


def _linear_pixels(root):
    from keystone_tpu.pipelines.images import linear_pixels as m

    if root is None:
        return m.run(m.LinearPixelsConfig(synthetic_n=1024))
    tr = os.path.join(root, "cifar", "train.bin")
    if not os.path.exists(tr):
        return None
    return m.run(
        m.LinearPixelsConfig(
            train_path=tr, test_path=os.path.join(root, "cifar", "test.bin")
        )
    )


def _cifar(root):
    from keystone_tpu.pipelines.images import random_patch_cifar as m

    if root is None:
        return m.run(
            m.RandomPatchCifarConfig(
                synthetic_n=768, num_filters=64, patch_sample=2000,
                num_iters=2, lam=5.0,
            )
        )
    tr = os.path.join(root, "cifar", "train.bin")
    if not os.path.exists(tr):
        return None
    return m.run(
        m.RandomPatchCifarConfig(
            train_path=tr, test_path=os.path.join(root, "cifar", "test.bin")
        )
    )


def _newsgroups(root):
    from keystone_tpu.pipelines.text import newsgroups as m

    if root is None:
        return m.run(m.NewsgroupsConfig(synthetic_n=600, num_features=500))
    tr = os.path.join(root, "newsgroups", "train")
    if not os.path.isdir(tr):
        return None
    return m.run(
        m.NewsgroupsConfig(
            train_path=tr, test_path=os.path.join(root, "newsgroups", "test")
        )
    )


def _amazon(root):
    from keystone_tpu.pipelines.text import amazon_reviews as m

    if root is None:
        return m.run(
            m.AmazonReviewsConfig(synthetic_n=600, num_features=500)
        )
    tr = os.path.join(root, "amazon", "train.jsonl")
    if not os.path.exists(tr):
        return None
    return m.run(
        m.AmazonReviewsConfig(
            train_path=tr, test_path=os.path.join(root, "amazon", "test.jsonl")
        )
    )


def _timit(root):
    from keystone_tpu.pipelines.speech import timit as m

    if root is None:
        return m.run(
            m.TimitConfig(
                synthetic_n=2048, num_features=1024, num_phones=12,
                num_iters=2, gamma=0.1,
            )
        )
    tr = os.path.join(root, "timit", "train.npz")
    if not os.path.exists(tr):
        return None
    return m.run(
        m.TimitConfig(
            features_path=tr,
            test_features_path=os.path.join(root, "timit", "test.npz"),
        )
    )


# Synthetic-run configs, shared by the runners AND the noise_band closed
# forms below (ADVICE r5: the band constants were independent hardcodes of
# these values — a drift in synthetic_classes/top_k would silently
# miscalibrate the band and pass out-of-band results).
VOC_SYNTH = dict(
    synthetic_n=96, synthetic_classes=4, pca_dims=24, gmm_k=4,
    descriptor_sample=20_000, num_iters=1,
)
IMAGENET_SYNTH = dict(
    synthetic_n=256, synthetic_classes=8, pca_dims=16, gmm_k=4,
    descriptor_sample=30_000, num_iters=1, top_k=5,
)


def _voc(root):
    from keystone_tpu.pipelines.images import voc_sift_fisher as m

    if root is None:
        return m.run(m.VOCSIFTFisherConfig(**VOC_SYNTH))
    img = os.path.join(root, "voc", "JPEGImages")
    if not os.path.isdir(img):
        return None
    return m.run(
        m.VOCSIFTFisherConfig(
            image_dir=img,
            annotation_dir=os.path.join(root, "voc", "Annotations"),
            test_image_dir=os.path.join(root, "voc", "TestJPEGImages"),
            test_annotation_dir=os.path.join(root, "voc", "TestAnnotations"),
        )
    )


def _imagenet(root):
    from keystone_tpu.pipelines.images import imagenet_sift_lcs_fv as m

    if root is None:
        return m.run(m.ImageNetSiftLcsFVConfig(**IMAGENET_SYNTH))
    tr = os.path.join(root, "imagenet", "train")
    if not os.path.isdir(tr):
        return None
    return m.run(
        m.ImageNetSiftLcsFVConfig(
            data_path=tr,
            test_data_path=os.path.join(root, "imagenet", "val"),
            label_map_path=os.path.join(root, "imagenet", "labels.txt"),
        )
    )


# name -> (runner, metric key, floor on real data, CI floor on synthetic,
#          higher_is_better, provenance)
# Real floors: BASELINE.md reference numbers (MNIST/CIFAR/TIMIT rows are
# low-confidence reconstructions — marked provisional). Synthetic floors:
# the test suite's pinned values (tests/test_*_pipeline*.py).
PIPELINES = {
    # CI floors assume the synthetic label-noise band (SYNTH_LABEL_NOISE
    # flips 10% of labels → even a perfect model scores ≈ 0.9 + 0.1/C on
    # accuracy metrics), so they sit BELOW the old separable-data values:
    # the run must land strictly between floor and ceiling to pass.
    "MnistRandomFFT": (_mnist, "test_accuracy", 0.96, 0.85, True, "BASELINE.md"),
    "LinearPixels": (_linear_pixels, "test_accuracy", 0.30, 0.50, True, "provisional"),
    "RandomPatchCifar": (_cifar, "test_accuracy", 0.80, 0.78, True, "BASELINE.md (84-85% full config)"),
    "NewsgroupsPipeline": (_newsgroups, "test_accuracy", 0.75, 0.80, True, "provisional"),
    # Amazon CI floor sits below the noisy-AUC ceiling (1-p = 0.90 at
    # p=0.1 — see noise_band) with a ≥0.10 window; 0.85 left only
    # [0.85, 0.90] and flaked (ADVICE r4).
    "AmazonReviewsPipeline": (_amazon, "auc", 0.85, 0.80, True, "provisional"),
    "TimitPipeline": (_timit, "phone_error_rate", 0.40, 0.20, False, "BASELINE.md (PER 33-34% full config)"),
    "VOCSIFTFisher": (_voc, "map", 0.45, 0.50, True, "provisional"),
    "ImageNetSiftLcsFV": (_imagenet, "top_k_error", 0.40, 0.60, False, "BASELINE.md (top-5 err 32-33% full config)"),
}

# Label-noise rate injected into the synthetic generators (overridable via
# a pre-set KEYSTONE_SYNTH_LABEL_NOISE). 0.1 puts every metric's
# best-possible value visibly below 1.0, making the floor/ceiling band
# meaningful.
SYNTH_LABEL_NOISE = 0.1


def noise_band(name: str, p: float):
    """Reachable-value band (lo, hi) for a pipeline's metric under the
    synthetic noise model (ADVICE r4: one accuracy-shaped band was
    miscalibrated for AUC / mAP / top-k error). ``None`` = unbounded side;
    the floor check already guards the other direction. Closed forms, all
    for a PERFECT model scored against noisy test labels:

    - accuracy — integer labels flip to a uniformly random OTHER class
      (synthetic.with_label_noise), so a flipped label never matches the
      true-class prediction: ceiling exactly 1-p, +p/2 realization slack.
    - AUC (balanced binary, flip rate p) — noisy-pos beats noisy-neg with
      prob (1-p)² + 2·½·p(1-p) = 1-p; ceiling 1-p, +p/4 slack.
    - multiclass error (PER) — perfect model errs on exactly the flipped
      fraction: floor p, ×½ slack.
    - top-k error (C classes) — a flipped label (uniform over C-1 others)
      still lands inside the model's remaining k-1 slots with prob
      (k-1)/(C-1): floor p·(C-k)/(C-1), ×½ slack.
    - mAP (per-ENTRY indicator flips, per-class prevalence π) — perfect
      ranking puts (1-p)·π·n kept positives on top (precision ≈ 1-p) and
      p·(1-π)·n flipped negatives uniform in the tail, where precision at
      depth t is ((1-p)π + p·t)/(π + t); integrating, the tail averages
      [p(1-π) + π(1-2p)·ln(1/π)]/(1-π). VOC synthetic prevalence is
      π = E[present classes]/C from the loader's own sampling rule.
      Ceiling + 0.05 slack (64-image test split is noisy).

    Every synthetic-run constant here (C, k, π) is read from VOC_SYNTH /
    IMAGENET_SYNTH / the VOC loader — the SAME objects the runners use —
    so the closed forms can't drift from the runs they bound (ADVICE r5).
    """
    import math

    from keystone_tpu.loaders.voc import VOCLoader

    acc_hi = 1.0 - p / 2.0
    def map_ceiling(pi):
        pos, neg = (1.0 - p) * pi, p * (1.0 - pi)
        tail = (p * (1.0 - pi) + pi * (1.0 - 2.0 * p) * math.log(1.0 / pi)) / (1.0 - pi)
        return (pos * (1.0 - p) + neg * tail) / (pos + neg)
    imagenet_c = IMAGENET_SYNTH["synthetic_classes"]
    imagenet_k = IMAGENET_SYNTH["top_k"]
    voc_pi = VOCLoader.SYNTH_PRESENT_CLASSES_MEAN / VOC_SYNTH["synthetic_classes"]
    bands = {
        "MnistRandomFFT": (None, acc_hi),
        "LinearPixels": (None, acc_hi),
        "RandomPatchCifar": (None, acc_hi),
        "NewsgroupsPipeline": (None, acc_hi),
        "AmazonReviewsPipeline": (None, (1.0 - p) + p / 4.0),
        "TimitPipeline": (p / 2.0, None),
        "ImageNetSiftLcsFV": (
            p * (imagenet_c - imagenet_k) / (imagenet_c - 1) / 2.0, None
        ),
        "VOCSIFTFisher": (None, map_ceiling(voc_pi) + 0.05),
    }
    return bands.get(name, (None, acc_hi if p < 0.5 else None))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("data_root", nargs="?", help="dataset root (see layout)")
    ap.add_argument("--synthetic", action="store_true",
                    help="run generated datasets with the CI floors")
    ap.add_argument("--pipelines", nargs="+", choices=sorted(PIPELINES),
                    help="subset to run (default: all)")
    ap.add_argument("--json", action="store_true",
                    help="also print one JSON line per pipeline")
    args = ap.parse_args(argv)
    if not args.synthetic and not args.data_root:
        ap.error("give a data root or --synthetic")
    root = None if args.synthetic else args.data_root

    # Synthetic mode injects the known label-noise overlap so the floors
    # BIND (a 1.0 score now means the band check failed, not success); an
    # explicitly pre-set env value wins, and the default is restored after
    # the run so in-process callers (tests) don't leak noise into other
    # synthetic users.
    noise_preset = os.environ.get("KEYSTONE_SYNTH_LABEL_NOISE")
    if args.synthetic and noise_preset is None:
        os.environ["KEYSTONE_SYNTH_LABEL_NOISE"] = str(SYNTH_LABEL_NOISE)
    from keystone_tpu.loaders.synthetic import label_noise_rate

    noise = label_noise_rate() if args.synthetic else 0.0

    names = args.pipelines or list(PIPELINES)
    rows, failures = [], 0
    def emit(name, key, value, floor, status, dt, note):
        """One JSON line per pipeline for EVERY outcome: ERROR rows carry
        the message, and every row says which backend actually ran (a CPU
        run must never be read back as chip evidence)."""
        if not args.json:
            return
        import jax

        print(json.dumps({"pipeline": name, "metric": key, "value": value,
                          "floor": floor, "status": status,
                          "ok": status == "PASS",
                          "backend": jax.default_backend(),
                          "note": note,
                          "seconds": round(dt, 1)}), flush=True)

    try:
        for name in names:
            runner, key, real_floor, ci_floor, higher, src = PIPELINES[name]
            floor = ci_floor if args.synthetic else real_floor
            t0 = time.time()
            try:
                out = runner(root)
            except Exception as e:  # a crash is a FAIL, not an abort
                err = f"{type(e).__name__}: {e}"
                dt = time.time() - t0
                rows.append((name, key, None, floor, "ERROR", dt, err))
                failures += 1
                emit(name, key, None, floor, "ERROR", dt, err)
                continue
            dt = time.time() - t0
            if out is None:
                rows.append((name, key, None, floor, "SKIP", dt, "no data"))
                emit(name, key, None, floor, "SKIP", dt, "no data")
                continue
            value = out.get(key)
            ok = value is not None and (
                value >= floor if higher else value <= floor
            )
            if ok and noise > 0.0:
                # The binding band: a score beyond the metric's noise-model
                # ceiling/floor (see noise_band) means the noise never
                # reached the metric — the harness is validating plumbing
                # again, not quality.
                lo, hi = noise_band(name, noise)
                band_ok = (lo is None or value >= lo) and (
                    hi is None or value <= hi
                )
                if not band_ok:
                    ok = False
                    bound = (f"> ceiling {hi:.4f}" if hi is not None
                             and value > hi else f"< floor {lo:.4f}")
                    src = (
                        f"OUT OF BAND (noise p={noise}, {bound}): metric "
                        "unreachable by a noisy-label run — floor not binding"
                    )
            status = "PASS" if ok else "FAIL"
            rows.append((name, key, value, floor, status, dt, src))
            if not ok:
                failures += 1
            emit(name, key, value, floor, status, dt, src)
    finally:
        if args.synthetic and noise_preset is None:
            del os.environ["KEYSTONE_SYNTH_LABEL_NOISE"]

    op = {True: ">=", False: "<="}
    print(f"\n{'pipeline':<22} {'metric':<18} {'value':>8} {'floor':>8}  verdict  {'sec':>7}  source")
    print("-" * 92)
    for name, key, value, floor, verdict, dt, src in rows:
        vs = "-" if value is None else f"{value:.4f}"
        sense = op[PIPELINES[name][4]]
        print(f"{name:<22} {key:<18} {vs:>8} {sense}{floor:<6.2f}  {verdict:<7} {dt:>6.1f}s  {src}")
    mode = "synthetic (CI floors)" if args.synthetic else f"real data at {root}"
    ran = sum(1 for r in rows if r[4] in ("PASS", "FAIL", "ERROR"))
    print(f"\n{mode}: {ran} ran, {failures} failed, "
          f"{sum(1 for r in rows if r[4] == 'SKIP')} skipped")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
