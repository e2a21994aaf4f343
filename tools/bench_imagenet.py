"""ImageNet SIFT+LCS+FV+BlockLS multi-device bench: the flagship chain
as scaling + donation evidence.

The ISSUE-16 tentpole claim, measured on the REAL pipeline (not the
synthetic matmul stand-in of ``bench_multichip.py``): synthetic-scale
ImageNet images through the actual two-branch featurizer — native dense
SIFT / LCS fronts, PCA, the PALLAS Fisher-vector kernel, signed-sqrt +
L2 — into the class-balanced block weighted least squares solver. Each
worker subprocess runs under a forced fake-device count
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``, the
``bench_multichip.py`` precedent) and A/Bs the sharded walk
(``config.shard_data_batches=True``: host descriptor batches staged onto
the mesh by the fused chain and donated where an output can alias them)
against the single-device walk; a third worker re-runs the wide mesh
with ``config.donate_buffers=False`` — the non-donated baseline the
KEYSTONE_DONATE_BUFFERS knob exists for.

Gates:

- **bit-identity (hard, always)**: sharded scores byte-equal to the
  single-device walk's at BOTH device counts, and the donated run
  byte-equal to the non-donated baseline — explicit specs, mask-padded
  scoring batches, staging donation, and the Pallas kernel must all be
  numerically invisible.
- **no silent fallback + Pallas active (hard, always)**: zero
  ``fallback_*`` counts, at least one sharded chain lowering, at least
  one ``pallas_sharded_calls`` (the FV kernel really ran on the sharded
  path), and at least one donation decision
  (``buffers_donated + donation_refused`` — the plumbing is live, with
  refusals counted, never silent).
- **rows/s scaling (hardware-conditional)**: hard (>= 0.7 * N/2) only on
  real hardware; soft (>= 0.25) on CPU fake devices, where the host
  SIFT/LCS fronts and time-sliced cores dominate (the PR-5/PR-9
  precedent).
- **peak HBM (hardware-conditional)**: donated run's
  ``peak_bytes_in_use`` strictly below the non-donated baseline's — only
  gateable where the runtime reports a peak (real hardware; CPU answers
  None, and the memory-attribution proof lives in
  tests/test_donated_fits.py via ``memory_analysis`` alias bytes).

The result row APPENDS to ``--out`` (BENCH_fit.json) as a fingerprinted
JSONL ``fit_imagenet_multichip`` row; ``make bench-watch`` learns the
family automatically (generic leaf flattening).

Usage: python tools/bench_imagenet.py [--devices 8] [--quick]
           [--out BENCH_fit.json]
Prints one JSON line; exit 1 on a failed hard gate.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Per-(device count, donate mode) worker. The whole flagship chain runs
#: in here; one JSON line comes back. Donation mode is decided before
#: anything lowers, so each subprocess's jit caches are pure per mode.
_WORKER = textwrap.dedent(
    """
    import hashlib, json, statistics, sys, time

    import jax
    import numpy as np

    from keystone_tpu.config import config
    from keystone_tpu.loaders.imagenet import ImageNetLoader
    from keystone_tpu.nodes.learning import BlockWeightedLeastSquaresEstimator
    from keystone_tpu.nodes.util import ClassLabelIndicators
    from keystone_tpu.pipelines.images.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
        build_featurizer,
        resolve_scale,
    )
    from keystone_tpu.utils.metrics import peak_hbm_bytes, sharding_counters
    from keystone_tpu.workflow.executor import PipelineEnv

    n, classes, reps = {n}, {classes}, {reps}
    config.donate_buffers = {donate!r}

    conf = resolve_scale(ImageNetSiftLcsFVConfig(
        synthetic_n=n, synthetic_classes=classes,
        pca_dims={pca_dims}, gmm_k={gmm_k}, gmm_iters=2,
        descriptor_sample=20000, fv_backend="pallas", num_iters=1,
    ))
    train, test = ImageNetLoader.synthetic(n=n, num_classes=classes)
    # Non-divisible held-out rows: every scoring pass exercises the
    # mask-pad path under the bit-identity gate.
    X_test = test.data[: max(66, len(test.data) - 3)]
    targets = np.asarray(ClassLabelIndicators(classes)(train.labels))

    def timed_fit(shard):
        PipelineEnv.reset()
        config.shard_data_batches = shard
        t0 = time.perf_counter()
        featurizer = build_featurizer(conf, train.data)
        solver = BlockWeightedLeastSquaresEstimator(
            block_size=conf.block_size, num_iters=conf.num_iters,
            lam=conf.lam, mixture_weight=conf.mixture_weight,
        )
        scored = featurizer.and_then(solver, train.data, targets)
        preds = np.asarray(scored(X_test).get())
        return time.perf_counter() - t0, preds

    # Warmup both walks so compile cost can't masquerade as scaling.
    timed_fit(False); timed_fit(True)

    unshard_walls, shard_walls = [], []
    preds_unshard = preds_shard = None
    for _ in range(reps):
        w, preds_unshard = timed_fit(False)
        unshard_walls.append(w)
    sharding_counters.reset()
    for _ in range(reps):
        w, preds_shard = timed_fit(True)
        shard_walls.append(w)
    counters = dict(sharding_counters.snapshot())

    out = {{
        "devices": len(jax.devices()),
        "backend": jax.default_backend(),
        "donate": bool(config.donate_buffers),
        "unshard_wall_s": statistics.median(unshard_walls),
        "shard_wall_s": statistics.median(shard_walls),
        "rows_per_s_sharded": n / statistics.median(shard_walls),
        "bit_identical": bool(np.array_equal(preds_unshard, preds_shard)),
        "preds_digest": hashlib.sha256(preds_shard.tobytes()).hexdigest(),
        "preds_norm": float(np.linalg.norm(preds_shard)),
        "counters": counters,
        "peak_hbm_bytes": peak_hbm_bytes(),
    }}
    print("IMAGENET_ROW " + json.dumps(out), flush=True)
    """
)


def _run_worker(n_devices: int, donate: bool, args) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}"
    )
    src = _WORKER.format(
        donate=donate, n=args.images,
        classes=args.classes, pca_dims=args.pca_dims, gmm_k=args.gmm_k,
        reps=args.reps,
    )
    proc = subprocess.run(
        [sys.executable, "-c", src], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"{n_devices}-device donate={donate} worker failed "
            f"rc={proc.returncode}\n"
            f"stdout:{proc.stdout[-1000:]}\nstderr:{proc.stderr[-2000:]}"
        )
    for line in proc.stdout.splitlines():
        if line.startswith("IMAGENET_ROW "):
            return json.loads(line[len("IMAGENET_ROW "):])
    raise RuntimeError(
        f"{n_devices}-device donate={donate} worker printed no row\n"
        f"stdout:{proc.stdout[-1000:]}"
    )


def run_bench(args) -> dict:
    one = _run_worker(1, True, args)
    multi = _run_worker(args.devices, True, args)
    baseline = _run_worker(args.devices, False, args)

    scaling = (
        multi["rows_per_s_sharded"] / one["rows_per_s_sharded"]
        if one["rows_per_s_sharded"] > 0 else float("inf")
    )
    bit_identical = bool(one["bit_identical"] and multi["bit_identical"])
    donation_invisible = bool(
        multi["preds_digest"] == baseline["preds_digest"]
    )
    c = multi["counters"]
    fallbacks = int(c.get("fallback_small_batch", 0)) + int(
        c.get("fallback_row_coupled", 0)
    )
    sharded_lowerings = int(c.get("sharded_chain_calls", 0))
    pallas_calls = int(c.get("pallas_sharded_calls", 0))
    donation_decisions = int(c.get("buffers_donated", 0)) + int(
        c.get("donation_refused", 0)
    )
    no_silent_fallback = fallbacks == 0 and sharded_lowerings > 0

    gate_is_hard = multi["backend"] != "cpu"
    bound = 0.7 * args.devices / 2 if gate_is_hard else 0.25
    scaling_gate = scaling >= bound

    # Peak-HBM gate: only where the runtime reports a peak (real
    # hardware). CPU answers None; the donated-below-undonated memory
    # proof there is the memory_analysis alias-bytes test in
    # tests/test_donated_fits.py.
    peak_d, peak_u = multi["peak_hbm_bytes"], baseline["peak_hbm_bytes"]
    peak_gate = True
    if gate_is_hard and peak_d is not None and peak_u is not None:
        peak_gate = peak_d < peak_u

    from keystone_tpu.utils.metrics import environment_fingerprint

    row = {
        "metric": "fit_imagenet_multichip",
        "value": round(scaling, 3),
        "unit": (
            "x rows_per_s scaling "
            f"({args.devices}-device sharded fit / 1-device sharded fit)"
        ),
        "backend": multi["backend"],
        "host_cores": os.cpu_count() or 1,
        "n_devices": args.devices,
        "env": environment_fingerprint(devices=False),
        "detail": {
            "images": args.images,
            "classes": args.classes,
            "pca_dims": args.pca_dims,
            "gmm_k": args.gmm_k,
            "reps": args.reps,
            "fv_backend": "pallas",
            "rows_per_s_1dev": round(one["rows_per_s_sharded"], 2),
            "rows_per_s_ndev": round(multi["rows_per_s_sharded"], 2),
            "wall_s_1dev": round(one["shard_wall_s"], 4),
            "wall_s_ndev": round(multi["shard_wall_s"], 4),
            "bit_identical": bit_identical,
            "donation_invisible": donation_invisible,
            "shard_fallbacks": fallbacks,
            "sharded_chain_calls": sharded_lowerings,
            "pallas_sharded_calls": pallas_calls,
            "buffers_donated": int(c.get("buffers_donated", 0)),
            "donation_refused": int(c.get("donation_refused", 0)),
            "batches_padded": int(c.get("batches_padded", 0)),
            "pad_rows_added": int(c.get("pad_rows_added", 0)),
            "no_silent_fallback": no_silent_fallback,
            "peak_hbm_donated": peak_d,
            "peak_hbm_undonated": peak_u,
            "peak_gate": peak_gate,
            "scaling_gate": scaling_gate,
            "scaling_gate_is_hard": gate_is_hard,
        },
    }
    row["ok"] = bool(
        bit_identical
        and donation_invisible
        and no_silent_fallback
        and pallas_calls > 0
        and donation_decisions > 0
        and peak_gate
        and (scaling_gate or getattr(args, "quick", False))
    )
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="multi-device ImageNet SIFT+LCS+FV+BlockLS fit bench"
    )
    ap.add_argument("--devices", type=int, default=8,
                    help="forced fake-device mesh width for the wide run")
    ap.add_argument("--reps", type=int, default=1,
                    help="fits per walk per worker; medians reported")
    ap.add_argument("--images", type=int, default=128,
                    help="synthetic train images (mesh-divisible)")
    ap.add_argument("--classes", type=int, default=8)
    ap.add_argument("--pca-dims", dest="pca_dims", type=int, default=8)
    ap.add_argument("--gmm-k", dest="gmm_k", type=int, default=4)
    ap.add_argument("--quick", action="store_true",
                    help="tiny problem — harness validation only, no row "
                         "is written and the scaling gate is soft")
    ap.add_argument("--out", default=None,
                    help="append the fingerprinted JSONL row here")
    args = ap.parse_args(argv)

    if args.quick:
        args.images, args.classes = 80, 4
        args.pca_dims, args.gmm_k, args.reps = 4, 2, 1

    row = run_bench(args)
    print(json.dumps(row), flush=True)

    if args.out and not args.quick:
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")

    d = row["detail"]
    if not d["bit_identical"]:
        print("GATE FAILED: sharded fit scores differ from the "
              "single-device walk", file=sys.stderr)
        return 1
    if not d["donation_invisible"]:
        print("GATE FAILED: donated fit scores differ from the "
              "non-donated baseline", file=sys.stderr)
        return 1
    if not d["no_silent_fallback"]:
        print(
            "GATE FAILED: sharded fit fell back single-device "
            f"(fallbacks={d['shard_fallbacks']}, "
            f"sharded_chain_calls={d['sharded_chain_calls']})",
            file=sys.stderr,
        )
        return 1
    if d["pallas_sharded_calls"] <= 0:
        print("GATE FAILED: the Pallas FV kernel never ran on the "
              "sharded path", file=sys.stderr)
        return 1
    if d["buffers_donated"] + d["donation_refused"] <= 0:
        print("GATE FAILED: no donation decision recorded — the donated "
              "lowering plumbing is not live", file=sys.stderr)
        return 1
    if not d["peak_gate"]:
        print(
            "GATE FAILED: donated peak HBM "
            f"{d['peak_hbm_donated']} not below non-donated "
            f"{d['peak_hbm_undonated']}",
            file=sys.stderr,
        )
        return 1
    if not d["scaling_gate"] and not args.quick:
        kind = "hard" if d["scaling_gate_is_hard"] else "soft"
        print(
            f"GATE FAILED: rows/s scaling {row['value']}x below the "
            f"{kind} bound at {row['n_devices']} devices",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
