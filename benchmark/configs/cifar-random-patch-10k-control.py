"""The planted faults of cifar-random-patch-10k, and the readings its limits
are set from, on the chip, at the cell's own size, many seeds in one process:

    python3 benchmark/configs/cifar-random-patch-10k-control.py --workload cifar-fit \
        --seeds 11,12,13 [--control-seeds 3] [--fault-seeds 3] [--out <file>]

It is ``tools/control.py`` (its arguments, its readings) with this
configuration's faults in the place of the image pipeline's: for each seed
the timed-path fit against the plain reference (the LOWER readings), for
the first ``--control-seeds`` the reference one precision step down put in
the program's place (UPPER readings), for the first ``--fault-seeds``
every fault of ``FAULTS``, each planted in the program's fit.
``tests/test_cifar_cell.py`` keeps them at sizes a test can hold.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "tools")]

import control


def patch_normalisation_left_out(adapter, data, sizes):
    """The patches go to the whitener and the filters as they are cut."""
    return adapter.fit(data, sizes, patch_norm=None)


def a_block_of_filters_zeroed(adapter, data, sizes):
    """An answer altered where it is produced: the first quarter of the
    fitted bank, and its bias, come back as nought."""
    fitted = adapter.fit(data, sizes)
    conv = adapter.parts_of(fitted)[0].stages[0]
    quarter = conv.num_filters // 4
    conv.filters = conv.filters.at[:quarter].set(0.0)
    conv.bias = conv.bias.at[:quarter].set(0.0)
    return fitted


def a_pooling_window_one_short(adapter, data, sizes):
    """Window 13 for 14: each pooled sum misses a row and a column."""
    return adapter.fit(data, sizes, pool_size=sizes["pool_size"] - 1)


def the_scaler_left_out(adapter, data, sizes):
    """The solver is handed the pooled sums unscaled: the scaler that is
    fitted takes nothing off and divides by one."""
    import jax.numpy as jnp

    from keystone_tpu.nodes.stats.scalers import StandardScaler, StandardScalerModel

    def idle(self, data):
        width = data.shape[1]
        return StandardScalerModel(jnp.zeros(width, data.dtype), jnp.ones(width, data.dtype))

    sound, StandardScaler.fit = StandardScaler.fit, idle
    try:
        return adapter.fit(data, sizes)
    finally:
        StandardScaler.fit = sound


def the_ragged_block_left_unsolved(adapter, data, sizes):
    """The last, narrower weight block of the fitted model comes back as
    zeros, as a solve that stopped at the whole blocks would leave it."""
    fitted = adapter.fit(data, sizes)
    mapper = adapter.parts_of(fitted)[2]
    mapper.W_blocks[-1] = mapper.W_blocks[-1] * 0.0
    return fitted


FAULTS = {
    "patch_normalisation_left_out": patch_normalisation_left_out,
    "a_block_of_filters_zeroed": a_block_of_filters_zeroed,
    "a_pooling_window_one_short": a_pooling_window_one_short,
    "the_scaler_left_out": the_scaler_left_out,
    "the_ragged_block_left_unsolved": the_ragged_block_left_unsolved,
    "half_the_batch": control.half_the_batch,
}


if __name__ == "__main__":
    control.FAULTS, control.REFERENCE_FAULTS = FAULTS, {}
    sys.exit(control.main())
