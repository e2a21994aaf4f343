"""Distributed linear algebra over a TPU device mesh.

The TPU-native rebuild of the reference's `amplab/ml-matrix` dependency
(RowPartitionedMatrix, TSQR, NormalEquations, BlockCoordinateDescent —
Ref: edu.berkeley.cs.amplab:mlmatrix, see SURVEY.md §2.2 [unverified]).

Spark `treeAggregate` tree-reductions become XLA `psum`/`all_gather`
collectives over the ICI mesh (emitted inside `shard_map` regions); the
per-partition Breeze gemms become per-chip MXU matmuls; the driver-side
Cholesky/QR solves become replicated on-device solves.
"""

from keystone_tpu.linalg.row_matrix import RowMatrix
from keystone_tpu.linalg.normal_equations import (
    solve_least_squares_chunked,
    solve_least_squares_normal,
)
from keystone_tpu.linalg.tsqr import tsqr_r, solve_least_squares_tsqr
from keystone_tpu.linalg.bcd import (
    BlockSource,
    assemble_blocks,
    block_coordinate_descent,
    block_coordinate_descent_generated,
    block_coordinate_descent_streamed,
)
from keystone_tpu.linalg.ring_bcd import block_coordinate_descent_ring

__all__ = [
    "RowMatrix",
    "BlockSource",
    "assemble_blocks",
    "solve_least_squares_normal",
    "solve_least_squares_chunked",
    "tsqr_r",
    "solve_least_squares_tsqr",
    "block_coordinate_descent",
    "block_coordinate_descent_streamed",
    "block_coordinate_descent_generated",
    "block_coordinate_descent_ring",
]
