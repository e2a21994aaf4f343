"""Readings that a cell's limits are set from, on the chip, at the cell's
own size, many seeds in one process (set-up is most of a run):

    python3 benchmark/tools/control.py --workload <cell> --seeds 11,12,13 \
        [--control-seeds 3] [--fault-seeds 3] [--out chiprun_out/control-<cell>.jsonl]

For each seed: one timed-path fit (the window's own call), its answers and
the plain reference (the LOWER readings: timed path against reference). For
the first ``--control-seeds`` seeds the reference again one precision step
down, put in the program's place (UPPER readings: control against
reference). For the first ``--fault-seeds`` seeds every fault below, read
the same way: ``REFERENCE_FAULTS`` break the reference's own fit, put in
the program's place, and ``FAULTS`` are planted in the program's fit. The
benchmark's runs never run these; ``tests/test_control.py`` and
``tests/test_faults.py`` keep them at sizes a test can hold.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The nearest precision below the one a configuration states.
STEP_DOWN = {"highest": "high", "high": "default"}


def half_the_batch(adapter, data, sizes):
    """Half of the batch left out: the fit sees the first half of the rows."""
    half = len(data["x"]) // 2
    return adapter.fit(dict(data, x=data["x"][:half], y=data["y"][:half]), sizes)


def a_block_left_unsolved(adapter, data, sizes):
    """An answer altered where it is produced: the first weight block of
    the fitted model comes back as zeros."""
    from program import linear_map

    fitted = adapter.fit(data, sizes)
    mapper = linear_map(fitted[-1] if isinstance(fitted, tuple) else fitted)
    mapper.W_blocks[0] = mapper.W_blocks[0] * 0.0
    return fitted


def one_em_sweep(adapter, data, sizes):
    """A step that returns its state all but unchanged: the mixture gets one
    EM sweep after its k-means start, of the configuration's twenty."""
    return adapter.fit(data, dict(sizes, gmm_iters=1))


FAULTS = {"half_the_batch": half_the_batch, "a_block_left_unsolved": a_block_left_unsolved,
          "one_em_sweep": one_em_sweep}

# Faults of the reference's own fit (``fault=`` of the adapter's reference),
# which a run of the program would cost a compile each to plant.
REFERENCE_FAULTS = {
    "ref_one_em_sweep": {"em_sweeps": 1},
    "ref_half_the_em_sweeps": {"em_sweeps": 10},
    "ref_half_the_sample": {"sample_share": 0.5},
    "ref_a_hundredth_of_the_sample": {"sample_share": 0.01},
}


def gaps(answers: dict, reference: dict, of_the_program: bool = False) -> dict:
    """Every ``<key>_gap`` both sides have an entry for. The program's
    tables are read by the reference, on its own data (``measured``)."""
    import harness

    if of_the_program:
        answers = dict(answers, **reference.get("measured", {}))
    keys = [k for k in reference if k in answers and k not in ("measured", "seconds")]
    limits = {k + "_gap": float("inf") for k in keys}
    return {k: v["value"] for k, v in harness.compare(answers, reference, limits).items()}


def readings(spec: dict, seed: int, with_control: bool, with_faults: bool, log) -> dict:
    import harness

    adapter, sizes = spec["adapter"], spec["sizes"]
    precision = spec["config"]["precision"]["reference"]
    data = adapter.make_data(seed, sizes)

    def fit_and_answer(fit):
        t = time.time()
        fitted = fit(adapter, data, sizes)
        fit_s = time.time() - t
        answers = adapter.answers(fitted, data, sizes)
        del fitted
        gc.collect()
        return answers, fit_s

    answers, fit_s = fit_and_answer(lambda a, d, s: a.fit(d, s))
    t = time.time()
    reference = adapter.reference(data, sizes, answers, precision)
    out = {"seed": seed, "fit_s": fit_s, "reference_s": time.time() - t,
           "reference_parts_s": reference.get("seconds"),
           "widths_off": harness.facts_gap(answers["facts"], adapter.expected_facts(sizes)),
           "read": {k: v for k, v in reference.items() if isinstance(v, float)},
           "program": gaps(answers, reference, True)}
    if with_control:
        control = adapter.reference(data, sizes, answers, STEP_DOWN[precision])
        out["control"] = gaps(control, reference)
        out["control_precision"] = STEP_DOWN[precision]
        del control
    if with_faults:
        out["faults"] = {}
        for name, fault in REFERENCE_FAULTS.items():
            broken = adapter.reference(data, sizes, answers, precision, fault=fault, solve=False)
            del broken["features"]  # the sound tables' features: nothing was broken there
            out["faults"][name] = gaps(broken, reference)
        for name, plant in FAULTS.items():
            broken, _ = fit_and_answer(plant)
            out["faults"][name] = gaps(
                broken, adapter.reference(data, sizes, broken, precision), True)
            del broken
    log(json.dumps(out))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu-ok", action="store_true",
                    help="rehearse on the CPU: the readings then mean nothing")
    args = ap.parse_args()

    import harness

    spec, device, _ = harness.prepare(args.workload, need_tpu=not args.cpu_ok)
    out = open(args.out, "a") if args.out else None

    def log(line):
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    log(json.dumps({"device": device, "cell": args.workload}))
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        readings(spec, seed, i < args.control_seeds, i < args.fault_seeds, log)
    return 0


if __name__ == "__main__":
    sys.exit(main())
