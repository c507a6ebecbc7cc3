"""Pallas TPU kernels (pl.pallas_call + BlockSpec) with jnp oracles.

  flash_attention/  blocked online-softmax attention (train/prefill/decode)
  moe_dispatch/     capacity-bucket grouped FFN (ZIPPER tiling over tokens)
  tile_spmm/        block-dense SpMM over graph tiles (the paper's dataflow)
  segment_softmax/  GAT edge softmax, single-pass online variant
  relation/         R-GCN typed aggregation: relation-grouped transform and
                    destination sum (kernel.py, ops.py; the oracle is
                    ``core.executor.typed_transform``)
Each provides kernel.py (Pallas), ops.py (jit wrapper), ref.py (oracle).
"""
