"""Custom TPU kernels (Pallas).

Hand-written kernels for hot ops where XLA's default scheduling leaves
HBM bandwidth on the table. Each kernel has an interpret-mode path so its
logic is exercised by the CPU-mesh test suite; on TPU the same code lowers
through Mosaic.
"""

from keystone_tpu.ops.conv_pool_pallas import conv_rectify_pool
from keystone_tpu.ops.fisher_vector_pallas import fisher_vectors_pallas

__all__ = ["conv_rectify_pool", "fisher_vectors_pallas"]
