"""What the adapters and the tools ask of a fitted KeystoneML pipeline:
the only helpers under ``benchmark/`` that import the program. The plain
references never come here."""

from __future__ import annotations

import numpy as np


def program_seed(seed: int) -> int:
    """The seed the program's own draws get (any run seed fits 31 bits)."""
    return int(seed) % (2**31 - 1)


def stages(pipeline) -> list:
    """The fitted transformers in topological order, fused chains opened."""
    out = []
    for t in pipeline.transformers():
        out.extend(getattr(t, "stages", [t]))
    return out


def linear_map(pipeline):
    """The one fitted ``BlockLinearMapper`` of ``pipeline``."""
    from keystone_tpu.nodes.learning.block_least_squares import BlockLinearMapper

    found = [s for s in stages(pipeline) if isinstance(s, BlockLinearMapper)]
    if len(found) != 1:
        raise AssertionError(f"expected one BlockLinearMapper, found {len(found)}")
    return found[0]


def wait_for(mapper) -> None:
    """A fit is over when every weight block is on the device and one
    element has reached the host."""
    for w in mapper.W_blocks:
        w.block_until_ready()
    np.asarray(mapper.W_blocks[-1][-1, -1])


def facts(mapper) -> dict:
    """The widths the fit really ran at, for ``widths_off``."""
    return {
        "feature_dim": int(mapper.blocks[-1][1]),
        "block_size": int(max(e - s for s, e in mapper.blocks)),
        "blocks": len(mapper.blocks),
        "classes": int(mapper.W_blocks[0].shape[1]),
    }
