"""Pallas TPU kernels for R-GCN's typed aggregation.

``relation_transform``: typed messages, one relation per row block.

Rows are typed edges grouped by relation (``tiling.relation_layout``) into
blocks of ``block_rows`` rows that all carry one relation.  The block's
relation id is scalar-prefetched and picks the weight block, so a run of
blocks of one relation keeps one weight block index, which the Pallas
pipeline does not copy again.  Each row is multiplied by its relation's
block-diagonal matrix in one of two forms:

* ``lanes`` (small blocks, such as R-GCN's 5x5): the features are laid out
  block-minor, column ``i * Bp + b`` holding input ``i`` of block ``b``
  (``Bp``: the block count rounded up to 128 lanes), so output ``o`` of
  every block is ``sum_i x[:, i] * Q[b, i, o]``: ``k`` lane-aligned f32
  multiply-adds on the VPU per output slice, with no MXU pass and no dense
  expansion of the blocks;
* ``dense`` (large blocks, such as one full matrix): the block-diagonal
  matrix expanded to (Fi, Fo) and one MXU dot at ``Precision.HIGHEST``.

``relation_sum``: the messages summed into their destinations.  Its tiles
hold message rows in destination order, each tile within one partition of
destinations (partition-major, the FIRST/LAST protocol of the tile
kernels); the kernel builds the tile's (destinations x rows) one-hot
selector from the rows' partition-local destinations (an iota compare, no
adjacency in memory) and accumulates ``selector @ rows`` on the MXU at
``Precision.HIGHEST``, which is exact for a 0/1 selector.

The kernel mode follows the backend (``tile_spmm.kernel.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tile_spmm import kernel as tile_kernel

#: the ``pallas_call`` names, which the compiled program's kernel ops carry
NAME = "relation_transform"
SUM_NAME = "relation_sum"
_HIGHEST = jax.lax.Precision.HIGHEST
FIRST, LAST = tile_kernel.FIRST, tile_kernel.LAST


def _lanes_kernel(rel_ref, x_ref, w_ref, o_ref, *, k: int, m: int, bp: int):
    for o in range(m):
        acc = x_ref[:, 0:bp] * w_ref[0, o:o + 1, :]
        for i in range(1, k):
            acc = acc + (x_ref[:, i * bp:(i + 1) * bp]
                         * w_ref[0, i * m + o:i * m + o + 1, :])
        o_ref[:, o * bp:(o + 1) * bp] = acc


def _dense_kernel(rel_ref, x_ref, w_ref, o_ref):
    o_ref[...] = jax.lax.dot(x_ref[...], w_ref[0], precision=_HIGHEST,
                             preferred_element_type=jnp.float32)


def relation_transform_pallas(xs, w, block_rel, *, block_rows: int,
                              form: str, k: int = 0, m: int = 0):
    """xs: (n_blocks * block_rows, W_in) rows in the form's feature layout;
    w: per relation, ``lanes``: (R, k * m, Bp) with row ``i * m + o``
    holding ``Q[r, :, i, o]`` over the block lanes, ``dense``: (R, W_in,
    W_out); block_rel: (n_blocks,) int32.  Returns (n_rows, W_out)."""
    n_rows, w_in = xs.shape
    n_blocks = n_rows // block_rows
    if form == "lanes":
        bp = w.shape[-1]
        body = functools.partial(_lanes_kernel, k=k, m=m, bp=bp)
        w_out = m * bp
    else:
        body = _dense_kernel
        w_out = w.shape[-1]
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,           # block_rel -> SMEM
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec((block_rows, w_in), lambda b, rel: (b, 0)),
                pl.BlockSpec((1,) + w.shape[1:],
                             lambda b, rel: (rel[b], 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, w_out), lambda b, rel: (b, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_rows, w_out), jnp.float32),
        interpret=tile_kernel.interpret_mode(),
        name=NAME,
    )(block_rel.astype(jnp.int32), xs.astype(jnp.float32),
      w.astype(jnp.float32))


def _sum_kernel(flags_ref, part_ref, d_ref, x_ref, o_ref, acc_ref):
    t = pl.program_id(0)
    flags = flags_ref[t]

    @pl.when(flags & FIRST != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dl = d_ref[0]                                   # (1, S) local dst, -1 pad
    sel = (jax.lax.broadcasted_iota(jnp.int32, (acc_ref.shape[0], dl.shape[1]),
                                    0) == dl).astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(sel, x_ref[0], precision=_HIGHEST,
                                preferred_element_type=jnp.float32)

    @pl.when(flags & LAST != 0)
    def _flush():
        o_ref[0] = acc_ref[...]


def relation_sum_pallas(dst_local, rows, part_id, flags, *, n_parts: int,
                        part_rows: int):
    """dst_local: (T, 1, S) int32 partition-local destination of each tile
    row (-1: padded); rows: (T, S, W) message rows; part_id/flags: (T,)
    int32, tiles partition-major.  Returns (n_parts, part_rows, W)."""
    T, S, W = rows.shape
    return pl.pallas_call(
        _sum_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,           # flags, part_id -> SMEM
            grid=(T,),
            in_specs=[
                pl.BlockSpec((1, 1, S), lambda t, flags, part: (t, 0, 0)),
                pl.BlockSpec((1, S, W), lambda t, flags, part: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, part_rows, W),
                                   lambda t, flags, part: (part[t], 0, 0)),
            scratch_shapes=[pltpu.VMEM((part_rows, W), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_parts, part_rows, W), jnp.float32),
        interpret=tile_kernel.interpret_mode(),
        name=SUM_NAME,
    )(flags.astype(jnp.int32), part_id.astype(jnp.int32),
      dst_local.astype(jnp.int32), rows.astype(jnp.float32))
