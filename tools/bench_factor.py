"""Factorization microbenchmark — where the BCD epoch's non-gemm time goes.

The round-3 solver rework replaced the per-epoch Cholesky solve with a
one-time explicit ridge inverse (NOTES_r3 §2) on the theory that TPU
lowers triangular solves sequentially while the inverse's per-epoch
apply is one MXU gemm. This tool measures the actual primitive costs on
the live backend so the tradeoff is grounded in silicon numbers, not
theory:

  gram        (n,b)ᵀ(n,b) gemm           — the MXU reference point
  cholesky    chol(b,b)                   — one-time, sequential lowering
  trsm_wide   inverse formation: two (b,b)×(b,b) triangular solves
  trsm_skinny cho_solve against k rhs     — the OLD per-epoch cost
  inv_gemm    (b,b)×(b,k) gemm            — the NEW per-epoch cost

Explicit inverse wins when
  trsm_wide < epochs · (trsm_skinny − inv_gemm),
i.e. above a break-even epoch count this tool prints per block size.

Usage: python tools/bench_factor.py [--blocks 1024 2048 4096 8192]
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


def _time(fn, *args, reps: int = 3) -> float:
    import jax

    out = fn(*args)  # compile + warm-up
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
        # Consume the result inside the timed region with a ONE-ELEMENT
        # host fetch (as bench.py does). Fetching the whole array would
        # time the D2H copy of (b,b) outputs but not (b,k) ones and skew
        # the break-even.
        float(jax.tree_util.tree_leaves(out)[0].ravel()[0])
    return (time.perf_counter() - t0) / reps


def measure_block(b: int, n: int, k: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_solve, solve_triangular

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(n, b)).astype(np.float32) / np.sqrt(n))
    rhs = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32))
    eye = jnp.eye(b, dtype=jnp.float32)

    gram_fn = jax.jit(lambda x: x.T @ x + 1e-3 * eye)
    chol_fn = jax.jit(jnp.linalg.cholesky)
    inv_fn = jax.jit(
        lambda L: solve_triangular(
            L, solve_triangular(L, eye, lower=True), lower=True, trans=1
        )
    )
    skinny_fn = jax.jit(lambda L, r: cho_solve((L, True), r))
    gemm_fn = jax.jit(lambda M, r: M @ r)

    gram = gram_fn(a)
    L = chol_fn(gram)
    inv = inv_fn(L)

    t_gram = _time(gram_fn, a)
    t_chol = _time(chol_fn, gram)
    t_wide = _time(inv_fn, L)
    t_skinny = _time(skinny_fn, L, rhs)
    t_gemm = _time(gemm_fn, inv, rhs)

    saving = t_skinny - t_gemm
    breakeven = (t_wide / saving) if saving > 1e-9 else float("inf")
    gram_tflops = 2.0 * n * b * b / t_gram / 1e12
    return {
        "block": b,
        "gram_s": round(t_gram, 5),
        "gram_tflops": round(gram_tflops, 2),
        "cholesky_s": round(t_chol, 5),
        "trsm_wide_s": round(t_wide, 5),
        "trsm_skinny_s": round(t_skinny, 6),
        "inv_gemm_s": round(t_gemm, 6),
        "breakeven_epochs": (
            round(breakeven, 1) if breakeven != float("inf") else None
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--blocks", type=int, nargs="+", default=[1024, 2048, 4096, 8192]
    )
    ap.add_argument("--n", type=int, default=32768)
    ap.add_argument("--k", type=int, default=16)
    args = ap.parse_args()

    from keystone_tpu.utils.platform import device_info

    backend = device_info()["platform"]
    rows = [measure_block(b, args.n, args.k) for b in args.blocks]
    print(
        json.dumps(
            {"metric": "bcd_factorization_primitives", "backend": backend,
             "rows": rows}
        )
    )


if __name__ == "__main__":
    main()
