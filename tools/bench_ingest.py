"""ImageNet ingest throughput benchmark (SURVEY.md §7 hard part 4).

Generates a synthetic-JPEG synset tree, then measures:
1. decode -> NHWC rate (images/sec) of the PIL thread pool at 256px,
   swept over worker counts;
2. the featurization rate of a representative conv patch-extraction step
   on the default backend;
3. overlapped streaming (decode-ahead batches feeding featurization)
   vs serial decode-then-featurize.

Usage: python tools/bench_ingest.py [--images 512] [--size 256]
Prints one JSON line.

--stream-solve switches to the chunked-solver overlap benchmark instead:
a synthetic out-of-core row stream whose producer is priced like a real
fixture read (simulated storage latency + zlib deserialize, both
GIL-releasing — the work a prefetch thread CAN overlap with compute)
feeds ``solve_least_squares_chunked`` serialized, synchronously
(prefetch_depth=0), and overlapped (the PrefetchIterator +
double-buffered H2D + donated accumulation path), and the line reports
the overlap ratios plus queue-depth-bounded peak-residency evidence
(``utils.metrics.peak_hbm_bytes`` where the runtime exposes it; the
host-side depth×batch bound always).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_jpeg_tree(root: str, n_images: int, size: int, synsets: int = 8) -> dict:
    """Class-textured JPEGs in <synset>/ dirs; returns the label map."""
    from PIL import Image

    rng = np.random.default_rng(0)
    label_map = {}
    base, extra = divmod(n_images, synsets)
    for s in range(synsets):
        name = f"n{s:08d}"
        label_map[name] = s
        d = os.path.join(root, name)
        os.makedirs(d, exist_ok=True)
        per = base + (1 if s < extra else 0)  # remainder distributed
        for i in range(per):
            x = rng.uniform(size=(size, size, 3))
            yy, xx = np.mgrid[0:size, 0:size]
            x[..., 0] = 0.5 + 0.5 * np.sin(2 * np.pi * (s + 2) / size * xx)
            img = Image.fromarray((x * 255).astype(np.uint8))
            img.save(os.path.join(d, f"img_{i:05d}.JPEG"), quality=90)
    return label_map


def stream_solve(args) -> None:
    """Synchronous vs overlapped out-of-core normal-equations ingest.

    The producer is a synthetic fixture READ priced like real out-of-core
    ingest: a simulated storage/network latency (``--io-ms``) plus a real
    zlib decompress + deserialize of the chunk — both release the GIL, as
    real file/socket I/O and codec work do, which is exactly the work a
    prefetch thread can overlap with compute. Three modes are timed
    (best-of ``--reps`` each, pipelines are latency-noisy on shared
    hosts):

    - serialized: prefetch_depth=0 under KEYSTONE_STREAM_NO_OVERLAP=1 —
      ingest and compute strictly alternate (the true no-overlap cost);
    - async-dispatch: prefetch_depth=0 as it ships — one thread, but
      XLA's async dispatch already pipelines compute under host work;
    - overlapped: the PrefetchIterator + double-buffered H2D + donated
      accumulation path.
    """
    from keystone_tpu.utils.platform import device_info

    backend = device_info()["platform"]
    import jax

    from keystone_tpu.linalg import solve_least_squares_chunked
    from keystone_tpu.loaders.stream import PrefetchIterator
    from keystone_tpu.utils.metrics import (
        environment_fingerprint,
        peak_hbm_bytes,
    )

    import zlib

    rows, d, k, chunks = args.chunk_rows, args.d, args.k, args.chunks
    depth, io_s = args.depth, args.io_ms / 1e3
    rng = np.random.default_rng(0)
    W_true = rng.normal(size=(d, k)).astype(np.float32)
    X0 = (rng.normal(size=(rows, d)) / np.sqrt(d)).astype(np.float32)
    Y0 = X0 @ W_true
    xblob = zlib.compress(X0.tobytes(), args.zlevel)
    yblob = zlib.compress(Y0.tobytes(), args.zlevel)

    def stream():
        for _ in range(chunks):
            time.sleep(io_s)  # storage/network latency stand-in
            X = np.frombuffer(zlib.decompress(xblob), dtype=np.float32)
            Y = np.frombuffer(zlib.decompress(yblob), dtype=np.float32)
            yield X.reshape(rows, d), Y.reshape(rows, k)

    def run_once(run_depth, serialize=False):
        # Pin the serialize knob BOTH ways: an inherited
        # KEYSTONE_STREAM_NO_OVERLAP=1 would otherwise silently turn the
        # async/overlapped reps into serialized ones.
        prior = os.environ.get("KEYSTONE_STREAM_NO_OVERLAP")
        if serialize:
            os.environ["KEYSTONE_STREAM_NO_OVERLAP"] = "1"
        else:
            os.environ.pop("KEYSTONE_STREAM_NO_OVERLAP", None)
        pf = None
        try:
            t0 = time.perf_counter()
            if run_depth > 0:
                pf = PrefetchIterator(stream(), run_depth)
                W = solve_least_squares_chunked(pf, lam=1e-3)
            else:
                W = solve_least_squares_chunked(
                    stream(), lam=1e-3, prefetch_depth=0
                )
            jax.block_until_ready(W)
            return time.perf_counter() - t0, pf
        finally:
            if prior is None:
                os.environ.pop("KEYSTONE_STREAM_NO_OVERLAP", None)
            else:
                os.environ["KEYSTONE_STREAM_NO_OVERLAP"] = prior

    # Producer-only cost, for the producer≈consumer context of the ratio.
    t0 = time.perf_counter()
    for _ in stream():
        pass
    producer_s = time.perf_counter() - t0

    run_once(0)  # warm both paths' compile caches
    run_once(depth)
    reps = max(1, args.reps)
    serial_s = min(run_once(0, serialize=True)[0] for _ in range(reps))
    async_s = min(run_once(0)[0] for _ in range(reps))
    timed = [run_once(depth) for _ in range(reps)]
    overlap_s, pf = min(timed, key=lambda t: t[0])

    chunk_bytes = rows * (d + k) * 4
    print(json.dumps({
        "metric": "stream_solve_overlap",
        "backend": backend,
        "host_cores": os.cpu_count(),
        "env": environment_fingerprint(),
        "chunks": chunks, "chunk_rows": rows, "d": d, "k": k,
        "io_ms": args.io_ms, "reps": reps,
        "producer_only_seconds": round(producer_s, 3),
        "sync_seconds": round(serial_s, 3),
        "async_dispatch_seconds": round(async_s, 3),
        "overlapped_seconds": round(overlap_s, 3),
        "overlap_ratio": round(serial_s / overlap_s, 3),
        "overlap_vs_async_ratio": round(async_s / overlap_s, 3),
        # Residency evidence: the queue can never hold more than depth
        # batches (max_queued is the observed high-water), so host
        # residency above the synchronous path is bounded by depth × chunk
        # bytes; on runtimes that report it, peak_hbm_bytes shows the
        # device side staying at two in-flight chunk buffers (donated
        # accumulation).
        "queue_depth": depth,
        "max_queued_batches": pf.max_queued if pf is not None else None,
        "host_residency_bound_bytes": depth * chunk_bytes,
        "chunk_bytes": chunk_bytes,
        "peak_hbm_bytes": peak_hbm_bytes(),
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=512)
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--workers", type=int, nargs="+", default=[4, 8, 16, 32])
    ap.add_argument("--stream-solve", action="store_true",
                    help="benchmark sync vs overlapped chunked solve "
                    "ingestion instead of the JPEG decode sweep")
    ap.add_argument("--chunks", type=int, default=16,
                    help="[stream-solve] chunks in the synthetic stream")
    ap.add_argument("--chunk-rows", type=int, default=2048,
                    help="[stream-solve] rows per chunk")
    ap.add_argument("--d", type=int, default=1024,
                    help="[stream-solve] feature dimension (defaults chosen "
                    "so producer cost ≈ consumer cost per chunk)")
    ap.add_argument("--k", type=int, default=8,
                    help="[stream-solve] target columns")
    ap.add_argument("--depth", type=int, default=2,
                    help="[stream-solve] prefetch queue depth")
    ap.add_argument("--io-ms", type=float, default=50.0,
                    help="[stream-solve] simulated storage latency per chunk")
    ap.add_argument("--zlevel", type=int, default=0,
                    help="[stream-solve] fixture compression level (0 = "
                    "stored blocks: pure deserialize, latency-dominated "
                    "producer — the stable default; raise it to price a "
                    "codec-heavy producer)")
    ap.add_argument("--reps", type=int, default=4,
                    help="[stream-solve] timing repetitions (best-of)")
    args = ap.parse_args()

    if args.stream_solve:
        stream_solve(args)
        return

    from keystone_tpu.utils.platform import device_info

    backend = device_info()["platform"]
    import jax
    import jax.numpy as jnp
    from jax import lax

    from keystone_tpu.loaders.imagenet import ImageNetLoader
    from keystone_tpu.utils.metrics import environment_fingerprint

    # The loader caps pool size at the core count (decode is CPU-bound;
    # NOTES_r2 §8's non-monotone sweep was oversubscription thrash on a
    # 1-core host), so requested counts above nproc clamp — the table
    # records the EFFECTIVE pool size.
    result: dict = {
        "metric": "imagenet_ingest",
        "backend": backend,
        "host_cores": os.cpu_count(),
        "env": environment_fingerprint(),
    }
    with tempfile.TemporaryDirectory() as root:
        label_map = make_jpeg_tree(root, args.images, args.size)

        # 1. raw decode rate: PIL thread pool per worker count vs the
        # native libjpeg/OpenMP pool. Save/restore any user override.
        decode = {}
        prior = os.environ.get("KEYSTONE_JPEG_BACKEND")
        try:
            os.environ["KEYSTONE_JPEG_BACKEND"] = "pil"
            from keystone_tpu.loaders.imagenet import _pool_workers

            for w in args.workers:
                eff = _pool_workers(w)
                key = f"pil-{eff}"
                if key in decode:
                    continue  # clamped to an already-measured pool size
                t0 = time.perf_counter()
                data = ImageNetLoader.load(
                    root, label_map, size=args.size, workers=w
                )
                dt = time.perf_counter() - t0
                decode[key] = round(len(data.data) / dt, 1)
            from keystone_tpu import native

            if native.jpeg_available():
                os.environ["KEYSTONE_JPEG_BACKEND"] = "native"
                t0 = time.perf_counter()
                data = ImageNetLoader.load(root, label_map, size=args.size)
                decode["native"] = round(
                    len(data.data) / (time.perf_counter() - t0), 1
                )
        finally:
            if prior is None:
                os.environ.pop("KEYSTONE_JPEG_BACKEND", None)
            else:
                os.environ["KEYSTONE_JPEG_BACKEND"] = prior
        result["decode_images_per_sec"] = decode
        best_rate = max(decode.values())

        # 2. featurization rate: conv patch extraction + pool, the front of
        # the RandomPatchCifar/ImageNet featurization stack.
        filters = jnp.asarray(
            np.random.default_rng(0).normal(size=(6, 6, 3, 64)) * 0.1,
            dtype=jnp.float32,
        )

        @jax.jit
        def featurize(X):
            out = lax.conv_general_dilated(
                X, filters, (2, 2), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            return jnp.maximum(out, 0.0).mean(axis=(1, 2))

        X0 = jnp.asarray(data.data[: args.batch])
        jax.block_until_ready(featurize(X0))  # compile
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            jax.block_until_ready(featurize(X0))
        feat_rate = args.batch * reps / (time.perf_counter() - t0)
        result["featurize_images_per_sec"] = round(feat_rate, 1)
        result["decode_feeds_featurization"] = best_rate >= feat_rate

        # 3. serial vs overlapped end-to-end
        t0 = time.perf_counter()
        data = ImageNetLoader.load(
            root, label_map, size=args.size, workers=16
        )
        for s in range(0, len(data.data), args.batch):
            jax.block_until_ready(
                featurize(jnp.asarray(data.data[s : s + args.batch]))
            )
        serial = time.perf_counter() - t0

        t0 = time.perf_counter()
        n = 0
        for X, _y in ImageNetLoader.stream_batches(
            root, label_map, batch_size=args.batch, size=args.size,
            workers=16,
        ):
            jax.block_until_ready(featurize(jnp.asarray(X)))
            n += len(X)
        overlap = time.perf_counter() - t0
        assert n == args.images
        result["serial_seconds"] = round(serial, 2)
        result["overlapped_seconds"] = round(overlap, 2)
        result["overlap_speedup"] = round(serial / overlap, 2)
        result["images"] = args.images
        result["px"] = args.size
    print(json.dumps(result))


if __name__ == "__main__":
    main()
