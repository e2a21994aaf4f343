"""Host-side descriptor throughput: native dense SIFT + LCS img/s per core.

The north-star projection (tools/northstar.py) shows the ImageNet
pipeline is HOST-bound on a v5e-64: the chips finish the FV encode and
the 64k-dim solve in seconds, so the budget hinges on how fast the host
fleet can decode + extract SIFT/LCS descriptors. Decode was measured in
NOTES_r3 §7 (273 img/s/core native at 512->256px); this tool measures
the missing piece — the clean-room C++ descriptor kernels
(native/src/sift.cpp, OpenMP) and the LCS extractor at the reference's
256px / step-4 configuration — so the projection's REQUIREMENT row can
be stated in cores, not hopes.

Usage: python tools/bench_host_featurize.py [--images 64] [--size 256]
Prints one JSON line. Pure host work: safe to run while the chip is dead.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(images: int, size: int, step: int) -> dict:
    from keystone_tpu.native import available
    from keystone_tpu.nodes.images.external.sift import SIFTExtractor
    from keystone_tpu.nodes.images.lcs import LCSExtractor

    rng = np.random.default_rng(0)
    gray = rng.uniform(size=(images, size, size)).astype(np.float32)
    rgb = rng.uniform(size=(images, size, size, 3)).astype(np.float32)

    out = {"images": images, "size": size, "step": step,
           "native_available": bool(available()),
           "host_cores": os.cpu_count()}
    if not available():
        return out

    sift = SIFTExtractor(step=step)
    lcs = LCSExtractor(step=step)

    for name, fn, data in (("sift", sift.apply_batch, gray),
                           ("lcs", lcs.apply_batch, rgb)):
        # Warm up at the FULL batch shape (first jnp trace compiles per
        # shape) and time through the host materialization — the LCS path
        # dispatches asynchronously, so the fetch IS part of the work.
        np.asarray(fn(data))
        t0 = time.perf_counter()
        d = np.asarray(fn(data))
        dt = max(time.perf_counter() - t0, 1e-9)
        out[f"{name}_img_per_sec"] = round(images / dt, 1)
        out[f"{name}_desc_per_img"] = int(d.shape[1]) if d.ndim >= 2 else None
        out[f"{name}_desc_dim"] = int(d.shape[-1])
    if out["sift_img_per_sec"] > 0 and out["lcs_img_per_sec"] > 0:
        both = 1.0 / (
            1.0 / out["sift_img_per_sec"] + 1.0 / out["lcs_img_per_sec"]
        )
        out["both_branches_img_per_sec"] = round(both, 1)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=64)
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--step", type=int, default=4)
    args = ap.parse_args()
    # HOST rates are the quantity under test: pin jax (the LCS extractor is
    # a jnp program) to CPU before any backend init — on the ambient TPU
    # platform this tool would otherwise measure the chip.
    # ONE OpenMP thread: the published rates are img/s PER CORE (that is
    # how northstar.py consumes them); the native SIFT kernel is OpenMP-
    # parallel and would otherwise report a per-process rate inflated by
    # nproc on multi-core hosts.
    os.environ["OMP_NUM_THREADS"] = "1"
    from keystone_tpu.utils.platform import force_cpu

    force_cpu()
    out = measure(args.images, args.size, args.step)
    out["omp_threads"] = 1
    print(json.dumps({"metric": "host_descriptor_img_per_sec", **out}))


if __name__ == "__main__":
    main()
