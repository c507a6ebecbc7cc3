"""Pallas TPU kernels (pl.pallas_call + BlockSpec) with jnp oracles.

  tile_spmm/        block-dense SpMM over graph tiles (the paper's dataflow)
                    and the GAT online segment softmax (kernel.py, ops.py,
                    ref.py)
  segment_softmax/  re-exports the segment-softmax entry points of tile_spmm
  relation/         R-GCN typed aggregation: relation-grouped transform and
                    destination sum (kernel.py, ops.py; the oracle is
                    ``core.executor.typed_transform``)
"""
