"""Label encoding nodes.

Ref: src/main/scala/nodes/util/ClassLabelIndicators.scala —
`ClassLabelIndicatorsFromIntLabels`: int label → dense ±1 indicator vector
(+1 at the class index, −1 elsewhere), the regression target encoding for
the least-squares classifiers [unverified].
"""

from __future__ import annotations

import jax.numpy as jnp

from keystone_tpu.config import config
from keystone_tpu.workflow import Transformer


class ClassLabelIndicators(Transformer):
    def __init__(self, num_classes: int):
        self.num_classes = num_classes
        # Resolved here, not at trace time: the program is shared by every
        # node of these two fields and would keep its first trace's dtype.
        self.dtype = config.default_dtype

    def signature(self):
        return self.stable_signature(self.num_classes)

    def apply_batch(self, y):
        y = jnp.asarray(y).astype(jnp.int32)
        onehot = jnp.zeros(
            (y.shape[0], self.num_classes), dtype=self.dtype
        )
        onehot = onehot.at[jnp.arange(y.shape[0]), y].set(1.0)
        return 2.0 * onehot - 1.0
