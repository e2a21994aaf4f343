"""Block-size / dtype MFU sweep for the BCD solver (BASELINE.md north-star
metric prep — VERDICT round-2 item 2).

For each (block, dtype) it runs ``bench.py`` in a child process, and reports
its TFLOPS/chip against the peak of that mode on the chip the child named.
Needs a TPU (``bench.py`` exits non-zero without one):

    python tools/bench_mfu.py --blocks 1024 2048 4096 8192 --dtypes f32 bf16

This parent never touches JAX: a chip belongs to one process at a time, and
each child takes it in turn. Prints one JSON line per config plus a final
summary table on stderr. Configs that clamp to the same effective block are
measured once.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # repo-root bench.py: the dtype modes (imports no jax)


def run_bench(env: dict, scale_key: str, dtype: str, timeout: float):
    """``bench.py`` in a child; its JSON line, or None with the child's
    stderr tail on ours."""
    cmd = [sys.executable, os.path.join(REPO, "bench.py"),
           "--scale", scale_key, "--dtype", dtype]
    try:
        proc = subprocess.run(
            cmd, env=env, capture_output=True, text=True, timeout=timeout
        )
    except subprocess.TimeoutExpired as e:
        tail = e.stderr or b""
        if isinstance(tail, bytes):
            tail = tail.decode(errors="replace")
        print(f"bench.py timed out; stderr tail:\n{tail[-2000:]}",
              file=sys.stderr)
        return None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        if isinstance(parsed, dict) and "metric" in parsed:
            return parsed
    print(f"bench.py rc={proc.returncode}, no JSON line; stderr tail:\n"
          f"{(proc.stderr or '')[-2000:]}", file=sys.stderr)
    return None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", type=int, nargs="+",
                    default=[1024, 2048, 4096, 8192])
    ap.add_argument("--dtypes", nargs="+", choices=sorted(bench.MXU_PASSES),
                    default=["f32", "bf16", "f32h"])
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--scale", choices=["tpu", "tpu-xl"], default="tpu",
                    help="tpu-xl = the reference-scale d=262144 config")
    args = ap.parse_args()

    from keystone_tpu.utils.metrics import environment_fingerprint

    # One provenance line up front (deviceless: this process never inits
    # the backend — the children do); each row then names its device.
    print(json.dumps({
        "metric": "env_fingerprint",
        **environment_fingerprint(devices=False),
    }), flush=True)

    rows = []
    for dtype in args.dtypes:
        seen_blocks = set()
        for block in args.blocks:
            env = dict(os.environ)
            env["KEYSTONE_BENCH_BLOCK"] = str(block)
            r = run_bench(env, args.scale, dtype, args.timeout)
            if r is None or r.get("value") is None:
                print(json.dumps(
                    {"block": block, "dtype": dtype, "error": "run failed"}
                ))
                continue
            actual_block = r["detail"]["block"]  # divisor-clamped by bench
            if actual_block in seen_blocks:
                continue
            seen_blocks.add(actual_block)
            mfu = r["value"] / r["detail"]["peak_tflops"]
            line = {
                "block": actual_block,
                "dtype": dtype,
                "backend": r.get("backend"),
                "device": r.get("device"),
                "tflops_per_chip": r["value"],
                "mfu_vs_plausible_peak": round(mfu, 4),
                "seconds_per_solve": r["detail"]["seconds_per_solve"],
                # Accuracy rides with speed (the f32h-vs-f32 decision
                # needs both).
                "relative_residual": r["detail"].get("relative_residual"),
            }
            rows.append(line)
            print(json.dumps(line), flush=True)

    if rows:
        print("\nblock  dtype  backend  TFLOPS/chip   MFU", file=sys.stderr)
        for r in rows:
            print(
                f"{r['block']:>5}  {r['dtype']:<5}  {r['backend']:<7}"
                f"  {r['tflops_per_chip']:>10.3f}  {r['mfu_vs_plausible_peak']:>6.2%}",
                file=sys.stderr,
            )


if __name__ == "__main__":
    main()
