"""The planted faults of timit-cosine-rf, and the readings its limits are
set from, on the chip, at the cell's own size, many seeds in one process:

    python3 benchmark/configs/timit-cosine-rf-control.py --workload timit-fit \
        --seeds 11,12,13 [--control-seeds 3] [--fault-seeds 3] [--out <file>]

It is ``tools/control.py`` (its arguments, its readings) with this
configuration's faults in the place of the image pipeline's: for each seed
the timed-path fit against the plain reference (the LOWER readings), for
the first ``--control-seeds`` the reference one precision step down put in
the program's place (UPPER readings), for the first ``--fault-seeds``
every fault of ``FAULTS``, each planted in the program's fit.
``tests/test_timit_cell.py`` keeps them at sizes a test can hold.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE), "tools")]

import control


def a_cosine_block_zeroed(adapter, data, sizes):
    """An answer altered where it is produced: the fitted cosine stage is
    replaced by one whose first block of columns comes out as nought."""
    from keystone_tpu.nodes.stats import CosineRandomFeatures

    class ZeroedBlock(CosineRandomFeatures):
        width = sizes["block_features"]

        def apply_batch(self, X):
            return super().apply_batch(X).at[:, :self.width].set(0.0)

    fitted = adapter.fit(data, sizes)
    chain, cosines = adapter.featurizer_of(fitted)
    chain.stages[1] = ZeroedBlock(cosines.W, cosines.b)
    return fitted


def an_epoch_left_out(adapter, data, sizes):
    """A step that returns its state all but unchanged: four epochs of the
    configuration's five."""
    return adapter.fit(data, dict(sizes, num_iters=sizes["num_iters"] - 1))


def one_block_drawn_for_all(adapter, data, sizes):
    """The draw that ignores the block's index: one block of columns,
    repeated side by side. Its standard deviation and its phases' spread
    are a sound draw's, and the features and scores follow it, so only the
    weights themselves, held to the reference's own draw, can tell."""
    import jax.numpy as jnp

    from keystone_tpu.nodes.stats import random_features

    def tiled(key, *, input_dim, block_features, blocks, distribution, dtype):
        W, b = random_features._draw(key, input_dim, block_features, distribution, dtype)
        return jnp.tile(W, (1, blocks)), jnp.tile(b, blocks)

    sound, random_features._draw_blocks = random_features._draw_blocks, tiled
    try:
        return adapter.fit(data, sizes)
    finally:
        random_features._draw_blocks = sound


def the_bandwidth_a_tenth_off(adapter, data, sizes):
    """The projection drawn at 1.1 gamma."""
    return adapter.fit(data, dict(sizes, gamma=1.1 * sizes["gamma"]))


def the_phases_on_half_the_circle(adapter, data, sizes):
    """b uniform on [0, pi): the fitted stage's phases halved after the
    fit, as a draw with the wrong upper end would have them."""
    fitted = adapter.fit(data, sizes)
    _chain, cosines = adapter.featurizer_of(fitted)
    cosines.b = cosines.b * 0.5
    return fitted


FAULTS = {
    "a_cosine_block_zeroed": a_cosine_block_zeroed,
    "an_epoch_left_out": an_epoch_left_out,
    "a_block_left_unsolved": control.a_block_left_unsolved,
    "one_block_drawn_for_all": one_block_drawn_for_all,
    "the_bandwidth_a_tenth_off": the_bandwidth_a_tenth_off,
    "the_phases_on_half_the_circle": the_phases_on_half_the_circle,
}


if __name__ == "__main__":
    control.FAULTS, control.REFERENCE_FAULTS = FAULTS, {}
    sys.exit(control.main())
