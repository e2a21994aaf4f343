"""The yardstick's arithmetic: chip peaks, and the canonical operations and
bytes of the block solver. Pure Python: nothing here imports the program.

``bcd_flops`` is a copy of ``bench.py``'s function of that name (the
original is listed in PERF.md for a later PR to delete); ``bcd_bytes`` is
new beside it.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def chip_peaks(device_kind: str) -> dict:
    """``{"bf16_tflops", "hbm_gbps", ...}`` of one chip of ``device_kind``.
    An unknown kind raises: a number measured against a guessed peak is
    worse than no number."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        chips = json.load(f)["chips"]
    if device_kind not in chips:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it to "
            "benchmark/peaks.json with its source"
        )
    return chips[device_kind]


def bcd_flops(n: int, d: int, k: int, block: int, iters: int) -> float:
    """Canonical FLOPs of block coordinate descent with cached ridge
    inverses: per block once, gram 2nb^2 + Cholesky b^3/3 + inverse 2b^3;
    per block and epoch, residual restore, right-hand side and residual
    update at 2nbk each and the inverse-multiply at 2b^2k. A fixed
    accounting, not a count of what one revision executes, so that a
    change of implementation never changes the yardstick."""
    nb = d // block
    once = 2.0 * n * block * block + block**3 / 3.0 + 2.0 * block**3
    per_epoch = 3 * 2.0 * n * block * k + 2.0 * block * block * k
    return nb * (once + per_epoch * iters)


def bcd_bytes(n: int, d: int, k: int, block: int, iters: int,
              itemsize: int = 4) -> float:
    """Least HBM traffic of the same solve, in bytes: A read once for the
    grams and three times an epoch (restore, right-hand side, update);
    each inverse written once and read once an epoch; per block visit the
    block's W read and written and the residual R read and written."""
    nb = d // block
    a = n * d * (1 + 3 * iters)
    inv = nb * block * block * (1 + iters)
    w = 2 * d * k * iters
    r = 2 * n * k * nb * iters
    return float(itemsize * (a + inv + w + r))


def roofline_seconds(flops: float, nbytes: float, peaks: dict,
                     chips: int = 1):
    """(seconds, bound): the least time ``chips`` chips could take, the
    larger of operations over peak FLOP/s and bytes over peak bytes/s, and
    which of the two it is."""
    t_flops = flops / (peaks["bf16_tflops"] * 1e12 * chips)
    t_bytes = nbytes / (peaks["hbm_gbps"] * 1e9 * chips)
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
