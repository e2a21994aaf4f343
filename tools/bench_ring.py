"""Ring (model-parallel) vs data-parallel BCD timing — when does the
d-sharded ring actually win?

``parallelism="model"`` (linalg/ring_bcd.py) shards the FEATURE axis and
rings n×k/P residual chunks over ppermute; ``parallelism="data"`` shards
rows and psums b×b grams. The docstring claim — ring wins when d dwarfs
n·k — had no timing behind it (VERDICT r4 weak #8). This tool times both
solvers on the same problem at a d≫n·k shape and a d≈n·k control shape,
on whatever backend is live:

- CPU 8-device mesh: the distributed SCHEDULE sanity check (collectives
  are emulated, so ratios bound program/schedule overhead, not ICI).
- TPU (one chip here): per-step program efficiency of the two lowerings
  at identical shapes; the ring's comm advantage needs a real multi-chip
  mesh, which this environment does not expose — recorded as such.

Prints ONE JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _solve_dp(A, B, block, iters, lam):
    import jax

    from keystone_tpu.linalg import RowMatrix, block_coordinate_descent

    Ma, Mb = RowMatrix.from_array(A), RowMatrix.from_array(B)
    W_blocks, _ = block_coordinate_descent(
        Ma, Mb, block_size=block, num_iters=iters, lam=lam, cache_grams=True
    )
    jax.block_until_ready(W_blocks[-1])
    return np.concatenate([np.asarray(w) for w in W_blocks], axis=0)


def _solve_ring(A, B, iters, lam):
    import jax

    from keystone_tpu.linalg import block_coordinate_descent_ring

    W = block_coordinate_descent_ring(A, B, num_iters=iters, lam=lam)
    jax.block_until_ready(W)
    return np.asarray(W)


def _timed(fn, reps):
    fn()  # compile + warm-up outside the timed region
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    dt = (time.perf_counter() - t0) / reps
    return out, dt


def measure(n, d, k, iters, lam, reps):
    import jax

    rng = np.random.default_rng(0)
    A = (rng.normal(size=(n, d)) / np.sqrt(d)).astype(np.float32)
    W_true = rng.normal(size=(d, k)).astype(np.float32)
    B = A @ W_true
    nshards = len(jax.devices())
    # d % nshards validated in main() (naming the offending flag).
    block = d // nshards  # DP uses the ring's per-chip block for parity

    W_dp, t_dp = _timed(lambda: _solve_dp(A, B, block, iters, lam), reps)
    W_ring, t_ring = _timed(lambda: _solve_ring(A, B, iters, lam), reps)

    bnorm = float(np.linalg.norm(B))
    return {
        "n": n, "d": d, "k": k, "iters": iters,
        "nk_over_d": round(n * k / d, 2),
        "block": block,
        "dp_seconds": round(t_dp, 4),
        "ring_seconds": round(t_ring, 4),
        "ring_speedup": round(t_dp / t_ring, 3),
        "dp_relative_residual": round(
            float(np.linalg.norm(A @ W_dp - B)) / bnorm, 5
        ),
        "ring_relative_residual": round(
            float(np.linalg.norm(A @ W_ring - B)) / bnorm, 5
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--d-wide", type=int, default=65536,
                    help="the d>>n*k shape (ring's home turf)")
    ap.add_argument("--d-control", type=int, default=8192,
                    help="a d~n*k control shape")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--lam", type=float, default=1e-3)
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args()

    from keystone_tpu.utils.platform import device_info

    backend = device_info()["platform"]
    import jax

    # Validate up front, naming the offending flag — a non-divisible d
    # otherwise surfaces deep in the solvers as an opaque shape error.
    ndev = len(jax.devices())
    for flag, d in (("--d-control", args.d_control), ("--d-wide", args.d_wide)):
        if d % ndev != 0:
            sys.exit(
                f"error: {flag}={d} is not divisible by the device count "
                f"({ndev}); the ring solver shards d per chip and the DP "
                "run reuses d // n_devices as its block size — pick a "
                f"multiple of {ndev}"
            )

    rows = [
        measure(args.n, d, args.k, args.iters, args.lam, args.reps)
        for d in (args.d_control, args.d_wide)
    ]
    print(json.dumps({
        "metric": "ring_vs_dp_bcd",
        "backend": backend,
        "n_devices": len(jax.devices()),
        "single_chip_note": (
            "ring comm advantage needs >1 chip; this row compares program "
            "schedules only" if len(jax.devices()) == 1 else None
        ),
        "rows": rows,
    }))


if __name__ == "__main__":
    main()
