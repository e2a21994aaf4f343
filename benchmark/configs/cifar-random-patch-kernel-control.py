"""The planted faults of cifar-random-patch-kernel, and the readings its
limits are set from, on the chip, at the cell's own size, many seeds in one
process:

    python3 benchmark/configs/cifar-random-patch-kernel-control.py \
        --workload cifar-kernel-fit --seeds 11,12,13 [--control-seeds 3] \
        [--fault-seeds 3] [--out <file>]

It is ``tools/control.py`` (its arguments, its readings) with this
configuration's faults in the place of the image pipeline's: for each seed
the timed-path fit against the plain reference (the LOWER readings), for
the first ``--control-seeds`` the reference one precision step down put in
the program's place (UPPER readings), for the first ``--fault-seeds``
every fault of ``FAULTS``, each planted in the program's fit: in one of the
three pieces of a visit that ``nodes/learning/kernel_ridge.py`` keeps apart
for this (the visits' order, the right-hand side, the diagonal), or in the
configuration. ``tests/test_cifar_kernel_cell.py`` keeps them at sizes a
test can hold.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "tools")]

import control


def _planted(adapter, data, sizes, piece, broken):
    """A fit with ``piece`` of the solver's visit replaced by
    ``broken(sound piece, *its arguments)``. The solver's program is traced
    anew for it, and again for the next sound fit."""
    import functools

    from keystone_tpu.nodes.learning import kernel_ridge

    sound = getattr(kernel_ridge, piece)
    kernel_ridge._block_solve_fn.cache_clear()
    setattr(kernel_ridge, piece, functools.partial(broken, sound))
    try:
        return adapter.fit(data, sizes)
    finally:
        setattr(kernel_ridge, piece, sound)
        kernel_ridge._block_solve_fn.cache_clear()


def the_diagonal_block_term_left_out(adapter, data, sizes):
    """R = Y_B - K_B^T alpha: what the block itself explains is taken off
    its own targets, from the second epoch on."""
    return _planted(
        adapter, data, sizes, "_block_residual",
        lambda sound, y_b, kt_alpha, k_bb, alpha_b, precision: y_b - kt_alpha)


def lam_left_off_the_diagonal(adapter, data, sizes):
    """K_BB is factorised as it is (the ragged block's pad keeps its 1)."""
    return _planted(adapter, data, sizes, "_ridge_diagonal",
                    lambda sound, col_live, lam: sound(col_live, 0.0 * lam))


def the_last_epoch_one_block_short(adapter, data, sizes):
    """The last visit of the last epoch is not made."""
    return _planted(adapter, data, sizes, "_visit_order",
                    lambda sound, num_blocks, num_epochs: sound(num_blocks, num_epochs)[:-1])


def the_ragged_block_left_unsolved(adapter, data, sizes):
    """Every epoch stops at the whole blocks: the last block's dual weights
    stay 0."""
    def whole_blocks(sound, num_blocks, num_epochs):
        visits = sound(num_blocks, num_epochs)
        return visits[visits != num_blocks - 1]

    return _planted(adapter, data, sizes, "_visit_order", whole_blocks)


def one_epoch_too_few(adapter, data, sizes):
    """A step that returns its state all but unchanged: two epochs of the
    configuration's three."""
    return adapter.fit(data, sizes, num_epochs=sizes["num_epochs"] - 1)


FAULTS = {
    "the_diagonal_block_term_left_out": the_diagonal_block_term_left_out,
    "lam_left_off_the_diagonal": lam_left_off_the_diagonal,
    "the_last_epoch_one_block_short": the_last_epoch_one_block_short,
    "the_ragged_block_left_unsolved": the_ragged_block_left_unsolved,
    "one_epoch_too_few": one_epoch_too_few,
}


if __name__ == "__main__":
    control.FAULTS, control.REFERENCE_FAULTS = FAULTS, {}
    sys.exit(control.main())
