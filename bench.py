"""Benchmark driver: one solver cell, one JSON line, one process.

Headline metric (BASELINE.md north star #2): solver TFLOPS/chip of the
block-least-squares inner loop — per-chip MXU gemms (residual update, gram,
gradient) + the row reduction over ICI + replicated Cholesky, the lowering
of the reference's BlockCoordinateDescent/treeAggregate stack (SURVEY.md
§3.2).

vs_baseline compares against a nominal 0.3 TFLOPS/node — the dgemm-class
throughput of one of the reference's EC2 r3.4xlarge CPU nodes (16 vcpus;
BASELINE.md has no published per-node figure, so this is a documented
engineering estimate for a sustained f64→f32-class BLAS3 workload).

The measurement runs in this process on the TPU JAX gives it and names that
device in its line. Without a TPU it exits non-zero and prints no number.
The timed loop forces a device-to-host fetch each rep and the result
carries a residual check.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

BASELINE_NODE_TFLOPS = 0.3
# Published per-chip peaks keyed by jax's ``device_kind``. A device that is
# not here is an error, never a default. Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}
# MXU passes per canonical gemm FLOP in each solver mode: bf16 storage is
# one pass, "f32h" (f32 storage, HIGH precision) three, f32 at HIGHEST six.
MXU_PASSES = {"bf16": 1, "f32h": 3, "f32": 6}


def peak_tflops(device_kind: str, dtype: str) -> float:
    """The chip's ceiling in canonical solver FLOPs for ``dtype``'s mode."""
    if device_kind not in CHIP_PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it to "
            "bench.CHIP_PEAKS with its source"
        )
    return CHIP_PEAKS[device_kind]["bf16_tflops"] / MXU_PASSES[dtype]


# Solver-code revision marker, stamped into every bench line so a row from
# an older solver is never read as this one's.
# r5: factor-phase rework, AOT-verified at the bench shapes — (a) identity
# RHS of the inverse's trsm is column-chunked (the unchunked program
# exceeded v5e HBM at the ImageNet shape); (b) one trsm + an MXU gemm
# (A⁻¹ = L⁻ᵀL⁻¹) replaces the chained pair, halving the sequential tail.
SOLVER_REV = "r5-trsm-gemm-inv"

# (n, d, k, block, iters) per scale.
SCALE = {
    "tpu": dict(n=32768, d=8192, k=16, block=4096, iters=2),
    # Reference-scale dimensionality (TIMIT 528k / CIFAR 256k features,
    # SURVEY.md §6): d >= 262144 exercises the many-block regime.
    # f32 residency: A (n·d·4B = 2 GiB) + the solver's a_blocks partition
    # copy (another 2 GiB — see bcd.py's slice-once note) + cached ridge
    # inverses (d·block·4B = 2 GiB) ≈ 6 GiB of v5e's 16 GiB, leaving
    # gram/Cholesky/inverse workspace headroom.
    "tpu-xl": dict(n=2048, d=262144, k=16, block=2048, iters=2),
    # The ImageNet headline shape (SURVEY.md §2.11 ImageNetSiftLcsFV:
    # 64k-dim FV features, k=1000 classes, 3 epochs): per-epoch gemms are
    # (n×b)·(b×1000) — real MXU work, unlike the k=16 rows whose skinny
    # epochs under-represent the shape the north star extrapolates to.
    # f32 residency: A 2 GiB + stacked-blocks copy 2 GiB + 8 cached ridge
    # inverses 2 GiB + W/R ≈ 0.3 GiB ≈ 6.3 GiB.
    "tpu-imagenet": dict(n=8192, d=65536, k=1000, block=8192, iters=3),
}


def bcd_flops(n: int, d: int, k: int, block: int, iters: int) -> float:
    """CANONICAL FLOPs of block_coordinate_descent's device work with gram
    caching: gram + Cholesky + explicit ridge inverse once per block, then
    per-epoch residual/rhs gemms and one inverse-multiply gemm (no
    triangular solves in the epoch loop).

    This is a FIXED accounting, not a per-revision raw-arithmetic count —
    TFLOPS stay comparable across solver revisions (r3/r4 rows, BASELINE
    ratios) as canonical-work/time. The formula charges the inverse at
    2·b³ (the two-trsm formation); the r5 implementation actually spends
    ~3·b³ there (one trsm + a full YᵀY gemm that ignores Y's
    triangularity), so reported TFLOPS slightly UNDERSTATE raw device
    throughput — the conservative direction."""
    nb = d // block
    # gram + Cholesky + canonical inverse formation (charged at 2·b³)
    once = 2.0 * n * block * block + block**3 / 3.0 + 2.0 * block**3
    per_epoch = (
        2.0 * n * block * k  # residual restore  A_b @ W_b
        + 2.0 * n * block * k  # rhs  A_bᵀR
        + 2.0 * block * block * k  # inverse-multiply solve gemm
        + 2.0 * n * block * k  # residual update
    )
    return nb * (once + per_epoch * iters)


def make_problem(rng, n: int, d: int, k: int, sparse_threshold: int = 1 << 25):
    """(A, B) with B exactly in A's column span.

    Huge-d·k scales (the ImageNet-shaped bench): a dense (d, k) W_true
    would cost ~n·d·k host FLOPs just to fabricate B. A W_true supported
    on 256 columns of every 8192-wide stripe (spread so no single feature
    block trivializes the solve) keeps B in-span at ~3% of the cost;
    solver FLOPs are value-independent, so the measurement is unchanged."""
    A = rng.normal(size=(n, d)).astype(np.float32)
    if d * k > sparse_threshold:
        stripe, per = 8192, 256
        support = np.concatenate(
            [np.arange(s, s + min(per, d - s)) for s in range(0, d, stripe)]
        )
        W_small = rng.normal(size=(support.size, k)).astype(np.float32)
        B = (A[:, support] @ W_small).astype(np.float32)
    else:
        W_true = rng.normal(size=(d, k)).astype(np.float32)
        B = (A @ W_true).astype(np.float32)
    return A, B


def measure(scale_key: str, dtype: str, device: dict) -> dict:
    """One measurement on this process's TPU (``device`` is
    ``platform.device_info()``'s description of it); returns the line."""
    from keystone_tpu.config import config
    from keystone_tpu.linalg import RowMatrix, block_coordinate_descent

    # The flag decides the measured mode outright — an ambient
    # KEYSTONE_SOLVER_DTYPE must never mislabel an f32 measurement.
    config.solver_storage_dtype = "bfloat16" if dtype == "bf16" else None
    # "f32h": f32 storage, HIGH (3-pass) matmul precision — the candidate
    # default the sweep measures against "highest" on the chip.
    config.solver_precision = "high" if dtype == "f32h" else "highest"

    p = SCALE[scale_key]
    n, d, k, block, iters = p["n"], p["d"], p["k"], p["block"], p["iters"]
    # Block-size override for the MFU sweep (tools/bench_mfu.py); clamped
    # to a divisor of d so the FLOP formula stays exact.
    env_block = os.environ.get("KEYSTONE_BENCH_BLOCK")
    if env_block:
        block = max(1, min(int(env_block), d))
        while d % block:
            block -= 1
    A, B = make_problem(np.random.default_rng(0), n, d, k)

    from keystone_tpu.linalg.row_matrix import storage_dtype

    Ma = RowMatrix.from_array(A, dtype=storage_dtype())
    Mb = RowMatrix.from_array(B)

    def run():
        # cache_grams pinned True so the timed path always matches bcd_flops.
        W, _blocks = block_coordinate_descent(
            Ma, Mb, block_size=block, num_iters=iters, lam=1e-3,
            cache_grams=True,
        )
        for w in W:
            w.block_until_ready()
        # A device→host fetch of the last element: the solve is consumed
        # inside the timed region.
        np.asarray(W[-1][-1, -1])
        return W

    W = run()  # warmup + compile
    # Validity check: a wrong or unconverged solve makes TFLOPS meaningless.
    West = np.concatenate([np.asarray(w) for w in W], axis=0)
    resid = float(np.linalg.norm(A @ West - B) / np.linalg.norm(B))
    # Two epochs cut the residual ~92% on this problem.
    assert resid < 0.2, f"BCD did not make progress (resid={resid})"

    # Time enough repetitions to amortize dispatch noise (>= 2s or 5 runs).
    reps, total = 0, 0.0
    while total < 2.0 and reps < 5:
        t0 = time.perf_counter()
        run()
        total += time.perf_counter() - t0
        reps += 1
    dt = total / reps

    from keystone_tpu.utils.metrics import environment_fingerprint, peak_hbm_bytes

    tflops_per_chip = (
        bcd_flops(n, d, k, block, iters) / dt / 1e12 / device["count"]
    )
    line = {
        "metric": "bcd_solver_tflops_per_chip",
        "value": round(tflops_per_chip, 3),
        "unit": "TFLOPS/chip",
        "vs_baseline": round(tflops_per_chip / BASELINE_NODE_TFLOPS, 2),
        "backend": device["platform"],
        "device": device,
        "env": environment_fingerprint(),
        "detail": {
            "n": n,
            "d": d,
            "k": k,
            "block": block,
            "epochs": iters,
            "dtype": dtype,
            "solver_rev": SOLVER_REV,
            "seconds_per_solve": round(dt, 4),
            "relative_residual": round(resid, 6),
            "devices": device["count"],
            "peak_hbm_bytes": peak_hbm_bytes(),
            "peak_tflops": round(peak_tflops(device["kind"], dtype), 1),
        },
    }
    if tflops_per_chip > line["detail"]["peak_tflops"]:
        line["suspect_timing"] = True
    return line


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=list(SCALE), default="tpu")
    # bf16 = store A in bfloat16, accumulate f32 (config.solver_storage_dtype).
    ap.add_argument("--dtype", choices=list(MXU_PASSES), default="f32")
    args = ap.parse_args()

    from keystone_tpu.utils.platform import device_info, setup_compile_cache

    setup_compile_cache()
    device = device_info(need_tpu=True)  # raises: exit code 1, no number
    peak_tflops(device["kind"], args.dtype)  # unknown kind: fail first
    print(json.dumps(measure(args.scale, args.dtype, device)), flush=True)


if __name__ == "__main__":
    main()
