"""North-star projection: ImageNet SIFT+LCS+FV+BWLS on a v5e-64, from
measured single-chip rates.

BASELINE.md's authoritative target is "ImageNet FV+BlockLS end-to-end
<= 10 min on TPU v5e-64, >= 10x the published 16-node EC2 baseline". No
64-chip slice exists in this environment, so this tool does the honest
next-best thing: a stage-by-stage bottleneck model whose inputs are
MEASURED single-chip numbers (per-step chip rows in TPU_REPORT.json)
wherever they exist, with every remaining constant printed as a labelled
assumption. Stages with no chip measurement say "not measured" and are
reported as REQUIRED rates (what the hosts/chips must sustain for the
10-min budget), not as claims. PR 21 deleted TPU_REPORT.json with the
harness that wrote it, so until a benchmark writes chip rows again every
chip stage reads "not measured".

This is a PROJECTION, not a measurement — the output says so.

Workload constants follow the reference pipeline (SURVEY.md §2.11
ImageNetSiftLcsFV [unverified]): N=1.28M train images, two descriptor
branches (SIFT + LCS) -> PCA(64) -> GMM(k=256) Fisher vectors -> 64k-dim
features -> BlockWeightedLeastSquares(k=1000).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # bcd_flops — the same FLOP model the measured TFLOPS uses

N_IMAGES = 1_281_167
K_CLASSES = 1000
D_FEATURES = 65_536
SOLVER_EPOCHS = 3
SOLVER_BLOCK = 8192  # matches bench.SCALE["tpu-imagenet"] (auto-sized r3 sweep)
CHIPS = 64
# Data-parallel BCD psums one b×b gram per block per epoch over ICI; on a
# 64-chip torus that collective overlaps poorly only at small n/chip.
# 0.8 is a stated assumption, not a measurement.
SCALING_EFFICIENCY = 0.8
DESCRIPTORS_PER_IMAGE = 2048  # dense-SIFT grid at 256px, step 4 (assumed)


def _report_steps() -> dict:
    try:
        with open(os.path.join(REPO, "TPU_REPORT.json")) as f:
            return json.load(f).get("steps", {})
    except (OSError, ValueError):
        return {}


K_GMM = 256  # GMM components per branch (2 branches x 2*64*256 = 64k dims)


def _tpu(steps: dict, name: str):
    rec = steps.get(name)
    if (
        rec
        and rec.get("backend") == "tpu"
        and rec.get("ok")
        and not rec.get("quick_scale")  # toy-scale rides are not evidence
    ):
        return rec
    return None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget-min", type=float, default=10.0)
    args = ap.parse_args()
    steps = _report_steps()
    try:
        with open(os.path.join(REPO, "HOSTBENCH.json")) as f:
            hb = json.load(f)
        if not float(hb.get("both_branches_img_per_sec") or 0) > 0:
            hb = None
    except (OSError, ValueError, TypeError):
        hb = None
    # Descriptor count: measured at the reference geometry when the host
    # bench ran; the 2048 constant otherwise.
    desc_per_img = (
        int(hb["sift_desc_per_img"]) if hb and hb.get("sift_desc_per_img")
        else DESCRIPTORS_PER_IMAGE
    )
    desc_basis = "measured" if hb else "assumed"
    rows = []

    # --- Solver: measured TFLOPS/chip × 64 chips × stated efficiency ----
    solver_flops = bench.bcd_flops(
        N_IMAGES, D_FEATURES, K_CLASSES, SOLVER_BLOCK, SOLVER_EPOCHS
    )
    # Prefer the AT-SHAPE measurement (bench_imagenet: d=65536, k=1000,
    # block=8192 on the chip) — its rate needs no transfer assumption. The
    # k=16 headline rows are the fallback, labelled as the rescale they are.
    shaped = _tpu(steps, "bench_imagenet")
    b = shaped or _tpu(steps, "bench_bf16") or _tpu(steps, "bench_f32")
    if b:
        tflops = b["tflops_per_chip"]
        dtype = b["bench_line"]["detail"]["dtype"]
        solver_s = solver_flops / (tflops * 1e12 * CHIPS * SCALING_EFFICIENCY)
        rate_basis = (
            "measured(tpu) AT ImageNet shape (d=65536, k=1000)"
            if shaped
            else "measured(tpu) at k=16 — RESCALED by FLOPs, assumes the "
            "rate transfers to k=1000"
        )
        rows.append(
            {
                "stage": f"BWLS solve (d=64k, k=1000, {SOLVER_EPOCHS} epochs)",
                "minutes": round(solver_s / 60, 2),
                "basis": f"{rate_basis}: {tflops} TFLOPS/chip ({dtype}) "
                f"x {CHIPS} chips x {SCALING_EFFICIENCY} eff (assumed)",
            }
        )
    else:
        rows.append(
            {
                "stage": "BWLS solve",
                "minutes": None,
                "basis": "not measured (no chip row for the solver)",
            }
        )

    # --- Fisher-vector encode on chip (both branches) -------------------
    fv = _tpu(steps, "pallas_fv")
    if fv:
        per_batch = min(
            t for t in (fv.get("pallas_s"), fv.get("xla_s")) if t
        )
        bsz = fv["config"]["batch"]
        m = fv["config"]["m"]
        k_meas = fv["config"]["k"]
        # Rescale the measured batch to the ImageNet shape: descriptor
        # count AND GMM component count (FV cost is linear in both), then
        # double for the two branches.
        per_img = (
            per_batch / bsz * (desc_per_img / m) * (K_GMM / k_meas) * 2
        )
        fv_s = N_IMAGES * per_img / CHIPS
        rows.append(
            {
                "stage": "FV encode (SIFT+LCS branches)",
                "minutes": round(fv_s / 60, 2),
                "basis": f"measured(tpu) {per_batch:.4f}s per {bsz}x{m} batch, "
                f"{desc_per_img} desc/img ({desc_basis}) x {CHIPS} chips",
            }
        )
    else:
        rows.append(
            {
                "stage": "FV encode",
                "minutes": None,
                "basis": "not measured (no chip row for the FV kernel)",
            }
        )

    # --- Sampled fits (PCA + GMM EM): negligible, shown with arithmetic --
    # PCA(64) on ~1M sampled descriptors and 25 EM iterations of a
    # k=256/d=64 GMM are ~2e12 matmul FLOPs per branch — sub-second at
    # even a tenth of the measured solver rate; listed so the stage
    # accounting is complete, not because it moves the total.
    rows.append(
        {
            "stage": "PCA + GMM fits (sampled)",
            "minutes": 0.1,
            "basis": "bounded: ~4e12 FLOPs total (2 branches) ≪ 1 chip-second"
            "; generous 0.1 min allowance",
        }
    )

    # --- Host-side decode + SIFT/LCS: required rate vs measured rate ----
    # Chip-stage total BEFORE the host rows append — the host rows carry
    # the remaining budget, not chip time.
    chip_minutes = round(sum(r["minutes"] or 0 for r in rows), 2)
    budget_s = args.budget_min * 60
    spent = sum(r["minutes"] or 0 for r in rows) * 60
    remaining = max(budget_s - spent, 0.0)
    req = N_IMAGES / remaining if remaining > 0 else float("inf")
    DECODE_PER_CORE = 273.0  # img/s/core, native pool 512->256px (round-3 host measurement)
    basis = (
        f"REQUIREMENT: fleet must sustain {req:,.0f} img/s aggregate in "
        "the remaining budget"
    )
    if hb is not None:
        both = float(hb["both_branches_img_per_sec"])
        per_core = 1.0 / (1.0 / both + 1.0 / DECODE_PER_CORE)
        cores = req / per_core if per_core > 0 else float("inf")
        basis += (
            f"; MEASURED host rates (tools/bench_host_featurize.py, "
            f"{hb['size']}px step {hb['step']}): SIFT "
            f"{hb['sift_img_per_sec']} + LCS {hb['lcs_img_per_sec']} "
            f"img/s/core -> {per_core:.1f} img/s/core incl. decode "
            f"=> ~{cores:,.0f} cores fleet-wide "
            f"(~{cores / 8:,.0f}/host on 8 hosts)"
        )
    else:
        basis += "; host descriptor rates unmeasured (run bench_host_featurize)"
    rows.append(
        {
            "stage": "host decode+SIFT+LCS",
            "minutes": round(remaining / 60, 2),
            "basis": basis,
        }
    )
    # Variant: --sift-backend xla moves dense SIFT onto the chips (LCS is
    # already a device program), leaving the hosts ONLY JPEG decode. The
    # on-chip SIFT adds ~1.3e8 conv FLOPs/image (two grouped 1-D convs
    # over an 8-channel orientation map) ≈ 5e12 FLOPs/chip total — a few
    # chip-seconds, bounded like the PCA/GMM row.
    rows.append(
        {
            "stage": "host decode ONLY (--sift-backend xla variant)",
            "minutes": round(remaining / 60, 2),
            "basis": f"with on-chip SIFT (ops/sift_xla.py): hosts need only "
            f"{req / DECODE_PER_CORE:,.0f} cores fleet-wide at the measured "
            f"{DECODE_PER_CORE:.0f} img/s/core decode rate; on-chip "
            "SIFT+LCS bounded at ~0.2 min across 64 chips",
        }
    )

    out = {
        "metric": "imagenet_northstar_projection_minutes",
        "note": "PROJECTION from measured single-chip rates; not a measurement",
        "target_minutes": args.budget_min,
        "baseline_minutes": 100.0,
        "chip_stages_minutes": chip_minutes,
        "stages": rows,
    }
    # Measured END-TO-END anchor: a pipeline_rate row is the whole
    # featurize→FV→solve program on one chip at full per-image geometry.
    # Its img/s cross-checks the sum-of-stage
    # model above — if the anchor disagrees with the stage sum, trust the
    # anchor.
    pr = _tpu(steps, "pipeline_rate")
    if pr and pr.get("featurize_img_per_sec"):
        img_s = float(pr["featurize_img_per_sec"])
        anchor_min = N_IMAGES / (img_s * CHIPS * SCALING_EFFICIENCY) / 60.0
        out["end_to_end_anchor"] = {
            "measured_img_per_sec_per_chip": img_s,
            "config": pr.get("config"),
            "stages_s": pr.get("stages_s"),
            "projected_chip_featurize_minutes_v5e64": round(anchor_min, 2),
            "basis": f"measured(tpu) end-to-end chip featurize "
            f"(on-chip SIFT+LCS+PCA+FV) x {CHIPS} chips x "
            f"{SCALING_EFFICIENCY} eff (assumed)",
        }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
