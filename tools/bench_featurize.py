"""Featurization throughput benchmark — the CIFAR conv front end on TPU.

Ref: src/main/scala/pipelines/images/cifar/RandomPatchCifar.scala's
featurization stage (Convolver + SymmetricRectifier + Pooler; SURVEY.md
§3.1) [unverified] — the reference runs this as per-image im2col+gemm
`mapPartitions` over EC2 CPU cores; here the whole chain is ONE fused XLA
program on the MXU (patches times filter bank + vector rectify +
`reduce_window` pool), measured in images/sec and conv TFLOPS/chip.

Timing discipline mirrors bench.py: a warm-up compile rep, then a timed
loop that forces a device-to-host fetch of a reduction each rep, so the
work is consumed inside the timed region.

Usage: python tools/bench_featurize.py [--filters 1024] [--batch 2048]
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def conv_flops(
    n: int, h: int, w: int, c: int, nf: int, fh: int, fw: int
) -> float:
    oh, ow = h - fh + 1, w - fw + 1
    return 2.0 * n * oh * ow * fh * fw * c * nf


def measure(batch: int, filters: int, dtype: str, reps: int) -> dict:
    import jax
    import jax.numpy as jnp

    from keystone_tpu.pipelines.images.random_patch_cifar import (
        RandomPatchCifarConfig,
        build_featurizer,
    )

    conf = RandomPatchCifarConfig(
        num_filters=filters,
        feature_dtype="bfloat16" if dtype == "bf16" else None,
        patch_sample=2048,
        synthetic_n=batch,
    )
    rng = np.random.default_rng(0)
    images = jnp.asarray(
        rng.uniform(size=(batch, 32, 32, 3)).astype(np.float32)
    )
    featurizer = build_featurizer(conf, images)

    def step(x):
        return featurizer(x).get()

    out = step(images)  # compile + warm-up
    feature_dim = int(np.prod(out.shape[1:]))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = step(images)
        # Force real device completion + transport each rep.
        float(jnp.sum(out[0]))
    dt = (time.perf_counter() - t0) / reps
    fl = conv_flops(batch, 32, 32, 3, filters, conf.patch_size, conf.patch_size)
    return {
        "batch": batch,
        "filters": filters,
        "dtype": dtype,
        "feature_dim": feature_dim,
        "images_per_sec": round(batch / dt, 1),
        "conv_tflops_per_chip": round(fl / dt / 1e12, 3),
        "seconds_per_batch": round(dt, 4),
    }


def measure_sift(batch: int, size: int, reps: int) -> dict:
    """On-chip dense SIFT (ops/sift_xla.py) img/s at the ImageNet geometry
    — the --sift-backend xla rate the north-star projection bounds."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.ops.sift_xla import dense_sift_xla

    rng = np.random.default_rng(0)
    imgs = jnp.asarray(
        rng.uniform(size=(batch, size, size)).astype(np.float32)
    )
    out = dense_sift_xla(imgs, step=4, bin_size=4)  # compile + warm-up
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = dense_sift_xla(imgs, step=4, bin_size=4)
        float(jnp.sum(out[0, 0]))  # force completion + tiny fetch
    dt = (time.perf_counter() - t0) / reps
    return {
        "kernel": "dense_sift_xla",
        "batch": batch,
        "size": size,
        "desc_per_img": int(out.shape[1]),
        "images_per_sec": round(batch / dt, 1),
        "seconds_per_batch": round(dt, 4),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--filters", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--sift-batch", type=int, default=64)
    ap.add_argument("--sift-size", type=int, default=256)
    ap.add_argument(
        "--dtypes", nargs="+", choices=["f32", "bf16"], default=["f32", "bf16"]
    )
    args = ap.parse_args()

    from keystone_tpu.utils.platform import device_info

    backend = device_info()["platform"]
    rows = [
        measure(args.batch, args.filters, d, args.reps) for d in args.dtypes
    ]
    rows.append(measure_sift(args.sift_batch, args.sift_size, args.reps))
    print(
        json.dumps(
            {
                "metric": "cifar_featurize_images_per_sec",
                "backend": backend,
                "rows": rows,
            }
        )
    )


if __name__ == "__main__":
    main()
