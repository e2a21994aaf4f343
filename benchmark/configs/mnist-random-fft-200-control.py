"""The planted faults of mnist-random-fft-200, and the readings its limits
are set from, on the chip, at the cell's own size, many seeds in one
process:

    python3 benchmark/configs/mnist-random-fft-200-control.py \
        --workload mnist-fft-fit --seeds 11,12,13 [--control-seeds 3] \
        [--fault-seeds 3] [--out <file>]

It is ``tools/control.py`` (its arguments, its readings) with this
configuration's faults in the place of the image pipeline's: for each seed
the timed-path fit against the plain reference (the LOWER readings), for
the first ``--control-seeds`` the reference one precision step down put in
the program's place (UPPER readings), for the first ``--fault-seeds``
every fault of ``FAULTS``, each planted in the program's fit: in one of the
two pieces of the generated-block solve that ``linalg/bcd.py`` keeps apart
for this (a block made again, a kept block read), in the stacking of the
branches, or in the configuration.
``benchmark/tests/test_mnist_cell.py`` keeps them at sizes a test can hold.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "tools")]

import control


def _planted(adapter, data, sizes, piece, broken):
    """A fit with ``piece`` of the generated-block solve replaced by
    ``broken(sound piece, *its arguments)``. The solver's program is traced
    anew for it, and again for the next sound fit."""
    import functools

    from keystone_tpu.linalg import bcd

    sound = getattr(bcd, piece)
    bcd._generated_solve_fn.cache_clear()
    setattr(bcd, piece, functools.partial(broken, sound))
    try:
        return adapter.fit(data, sizes)
    finally:
        setattr(bcd, piece, sound)
        bcd._generated_solve_fn.cache_clear()


def a_regenerated_block_from_the_wrong_signs(adapter, data, sizes):
    """A block made again in a later epoch is made from the next block's
    branches: the wrong branches' signs."""
    return _planted(adapter, data, sizes, "_remake",
                    lambda sound, generator, xl, b: sound(generator, xl, b + 1))


def a_kept_block_read_at_the_wrong_index(adapter, data, sizes):
    """A later epoch reads the next slot of the store for a kept block."""
    def next_slot(sound, store, b):
        import jax.numpy as jnp

        return sound(store, jnp.remainder(b + 1, store.shape[0]))

    return _planted(adapter, data, sizes, "_fetch", next_slot)


def the_branches_stacked_in_reverse(adapter, data, sizes):
    """The gathered branches stacked last first: every block made from
    other branches' signs than its own, in the fit and in the fitted map."""
    from keystone_tpu.workflow.pipeline import BranchStack

    sound = BranchStack.of
    BranchStack.of = staticmethod(lambda chains, block, x: sound(chains[::-1], block, x))
    try:
        return adapter.fit(data, sizes)
    finally:
        BranchStack.of = sound


def one_epoch_too_few(adapter, data, sizes):
    """A step that returns its state all but unchanged: two epochs of the
    configuration's three."""
    return adapter.fit(data, sizes, num_epochs=sizes["num_epochs"] - 1)


FAULTS = {
    "a_regenerated_block_from_the_wrong_signs": a_regenerated_block_from_the_wrong_signs,
    "a_kept_block_read_at_the_wrong_index": a_kept_block_read_at_the_wrong_index,
    "the_branches_stacked_in_reverse": the_branches_stacked_in_reverse,
    "one_epoch_too_few": one_epoch_too_few,
}


if __name__ == "__main__":
    control.FAULTS, control.REFERENCE_FAULTS = FAULTS, {}
    sys.exit(control.main())
