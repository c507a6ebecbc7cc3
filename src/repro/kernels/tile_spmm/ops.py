"""Jitted wrappers: TileSet -> block-dense tensors -> Pallas tile kernels.

``densify_tiles`` turns a ZIPPER :class:`TileSet` (or each bucket of a
:class:`BucketedTileSet`) plus source features into the (adj, xsrc)
block-dense form the TPU kernels consume.  ``spmm`` / ``gat_aggregate`` are
the public entry points: the GNN benchmarks call them directly, and
``core/pipeline.py`` passes ``spmm`` as ``tile_kernel`` so pure-SpMM gather
phases run on the Pallas kernel (one call per size bucket, partition
outputs summed across buckets) instead of the ``lax.scan`` body.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ...core.tiling import BucketedTileSet, TileSet
from .kernel import (segment_softmax_csr_pallas, segment_softmax_pallas,
                     tile_flags, tile_spmm_csr_pallas, tile_spmm_pallas)


def densify_tiles(tiles: Union[TileSet, BucketedTileSet],
                  edge_weight: Optional[np.ndarray] = None):
    """Build dense per-tile adjacency blocks A (T, Dmax, Smax).

    A[t, d, s] = sum of weights of edges (s -> d) in tile t (1.0 default).
    Also returns the FIRST/LAST flags.  numpy, one-time preprocessing —
    the analogue of the paper's offline tiling pass.

    For a :class:`BucketedTileSet` the result is one (adj, flags) pair per
    bucket — Smax differs per bucket (that is the point of bucketing) while
    Dmax stays the shared partition maximum, so per-bucket kernel outputs
    can be summed into one (P, Dmax, F) accumulator.
    """
    if isinstance(tiles, BucketedTileSet):
        return [densify_tiles(b, edge_weight) for b in tiles.buckets]
    T, S = tiles.edge_src.shape
    D = int(tiles.part_size.max())
    Smax = tiles.s_max
    adj = np.zeros((T, D, Smax), np.float32)
    for t in range(T):
        ne = int(tiles.n_edge[t])
        w = np.ones(ne, np.float32) if edge_weight is None else \
            edge_weight[tiles.edge_gid[t, :ne]]
        np.add.at(adj[t], (tiles.edge_dst[t, :ne], tiles.edge_src[t, :ne]), w)
    return adj, tile_flags(tiles.part_id)


def gather_sources(tiles: Union[TileSet, BucketedTileSet], x):
    """(T, Smax, F) compacted source features (sparse tiling's gather);
    one array per bucket for a :class:`BucketedTileSet`."""
    if isinstance(tiles, BucketedTileSet):
        return [gather_sources(b, x) for b in tiles.buckets]
    return jnp.asarray(x)[jnp.asarray(tiles.src_ids)]


_NEG = -1e30  # matches the segment-softmax kernel's "no edge" sentinel


@functools.partial(jax.jit, static_argnames=("dmax", "smax"))
def densify_edge_weights(weights, edge_dst, edge_src, n_edge, *,
                         dmax: int, smax: int):
    """Runtime analogue of :func:`densify_tiles` for *computed* edge weights.

    weights: (T, Emax) per-edge scalars (e.g. attention α evaluated on the
    edge segment); edge_dst/edge_src: (T, Emax) tile-local indices; n_edge:
    (T,) true counts.  Returns (T, dmax, smax) dense adjacency blocks with
    parallel edges summed — the A operand of the weighted-SpMM kernel block.
    """
    T, E = weights.shape
    emask = jnp.arange(E)[None, :] < n_edge[:, None]
    w = jnp.where(emask, weights, 0.0).astype(jnp.float32)

    def per_tile(w_t, ed, es):
        return jnp.zeros((dmax, smax), jnp.float32).at[ed, es].add(w_t)

    return jax.vmap(per_tile)(w, edge_dst, edge_src)


@functools.partial(jax.jit, static_argnames=("dmax",))
def densify_edge_scores(scores, edge_dst, n_edge, *, dmax: int):
    """Per-edge-COLUMN score densification for the segment-softmax kernel.

    scores: (T, Emax) per-edge attention logits.  Returns (T, dmax, Emax)
    blocks where column ``j`` holds edge ``j``'s score at its destination row
    and the ``_NEG`` sentinel everywhere else.  Giving every edge its own
    column (instead of compacting onto source columns) keeps parallel edges
    in separate softmax slots, so multigraphs stay exact.
    """
    T, E = scores.shape
    emask = jnp.arange(E)[None, :] < n_edge[:, None]
    s = jnp.where(emask, scores, _NEG).astype(jnp.float32)

    def per_tile(s_t, ed):
        return jnp.full((dmax, E), _NEG, jnp.float32).at[ed, jnp.arange(E)].set(s_t)

    return jax.vmap(per_tile)(s, edge_dst)


# Kernel entry points.  The backend decides the Pallas mode
# (:func:`~repro.kernels.tile_spmm.kernel.interpret_mode`); the ``*_ref``
# oracles in ``ref.py`` are for tests only.
spmm = jax.jit(tile_spmm_pallas, static_argnames=("n_parts",))
gat_aggregate = jax.jit(segment_softmax_pallas, static_argnames=("n_parts",))

# CSR-within-tile entry points: no densify pass — ``col`` IS the CSR-ordered
# ``edge_src`` and weights/scores stay per-edge vectors.
spmm_csr = jax.jit(tile_spmm_csr_pallas, static_argnames=("n_parts",))
gat_aggregate_csr = jax.jit(segment_softmax_csr_pallas,
                            static_argnames=("n_parts",))
