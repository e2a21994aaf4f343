"""The planted faults of imagenet-sift-lcs-fv-64k-x4, and the readings its
limits are set from, on four chips, at the cell's own size, many seeds in
one process:

    python3 benchmark/configs/imagenet-sift-lcs-fv-64k-x4-control.py \
        --workload imagenet-fit-4chip --seeds 11,12,13 [--control-seeds 1] \
        [--fault-seeds 1] [--faults a,b] [--out <file>]

For each seed the timed-path fit against the plain reference (the LOWER
readings); for the first ``--control-seeds`` the reference one precision
step down put in the program's place, up to the held-out features (its
solve is not read: a four-chip minute each, and the one-chip
configuration's control never reached ``scores_gap``); for the first
``--fault-seeds`` the named ``FAULTS`` (all of them by default), each
planted in the program's fit. The deployment's own fault is a shard's rows
left out of one reduction across the mesh: ``sharded_rowsum`` as the solver
or the mixture's fit sees it, with shard 0's partial sum made nought under
one ``coll.`` scope. ``tests/test_imagenet_4chip_cell.py`` keeps them at
sizes a test can hold.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "tools")]

import control


def _without_shard_0(module, scope: str, leaf: int | None, forget):
    """``module.sharded_rowsum`` with shard 0's partial sums (of leaf
    ``leaf`` of what is summed, or of all) left out of the reductions under
    ``scope``, as a context: the programs that traced the sound reduction
    are forgotten going in (``forget``), and the faulty ones coming out."""
    import contextlib

    import jax
    from jax import lax

    sound = module.sharded_rowsum

    def leaky(block_fn, axis, width, operands, row_axes=None, *, scope: str):
        if scope != leaky.scope:
            return sound(block_fn, axis, width, operands, row_axes, scope=scope)

        def without(*rows):
            parts = block_fn(*rows)
            leaves, tree = jax.tree_util.tree_flatten(parts)
            held = lax.axis_index(axis) != 0
            leaves = [v * held.astype(v.dtype) if leaf in (None, i) else v
                      for i, v in enumerate(leaves)]
            return jax.tree_util.tree_unflatten(tree, leaves)

        return sound(without, axis, width, operands, row_axes, scope=scope)

    leaky.scope = scope

    @contextlib.contextmanager
    def planted():
        forget()
        module.sharded_rowsum = leaky
        try:
            yield
        finally:
            module.sharded_rowsum = sound
            forget()

    return planted()


def a_shard_left_out_of_the_grams(adapter, data, sizes):
    """One shard's rows left out of one reduction: every block's gram is
    summed over three of the four shards (the ridge inverses are then of
    another matrix), everything else over all four."""
    from keystone_tpu.linalg import bcd

    def forget():
        bcd._fused_factor_fn.cache_clear()
        bcd._fused_epochs_fn.cache_clear()

    with _without_shard_0(bcd, "coll.gram", None, forget):
        return adapter.fit(data, sizes)


def a_shard_left_out_of_the_em_mass(adapter, data, sizes):
    """The same in the mixtures' fit: every EM sweep's mass is summed over
    three of the four shards, its two moments over all four, so the means
    come out a third too large."""
    from keystone_tpu.nodes.learning import gmm

    with _without_shard_0(gmm, "coll.em", 0, gmm._fit_gmm.clear_cache):
        return adapter.fit(data, sizes)


FAULTS = {
    "a_shard_left_out_of_the_grams": a_shard_left_out_of_the_grams,
    "a_shard_left_out_of_the_em_mass": a_shard_left_out_of_the_em_mass,
    "a_block_left_unsolved": control.a_block_left_unsolved,
    "half_the_batch": control.half_the_batch,
}


def readings(spec: dict, seed: int, with_control: bool, faults, log) -> dict:
    """``control.readings`` with the control stopped at the held-out
    features and the faults chosen by name."""
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
           "program": control.gaps(answers, reference, True)}
    if with_control:
        step_down = control.STEP_DOWN[precision]
        lower = adapter.reference(data, sizes, answers, step_down, solve=False)
        out["control"] = control.gaps(lower, reference)
        out["control_precision"] = step_down
        del lower
    out["faults"] = {}
    for name in faults:
        broken, _ = fit_and_answer(FAULTS[name])
        out["faults"][name] = control.gaps(
            broken, adapter.reference(data, sizes, broken, precision), True)
        del broken
    log(json.dumps(out))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--fault-seeds", type=int, default=0)
    ap.add_argument("--faults", default=",".join(FAULTS))
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
        readings(spec, seed, i < args.control_seeds,
                 args.faults.split(",") if i < args.fault_seeds else [], log)
    return 0


if __name__ == "__main__":
    sys.exit(main())
