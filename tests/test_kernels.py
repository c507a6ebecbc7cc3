"""Tile SpMM and segment-softmax kernels: Pallas (interpret mode) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.tile_spmm import ops as tops
from repro.kernels.tile_spmm.ref import segment_softmax_ref, tile_spmm_ref
from repro.core import tiling
from repro.gnn import graphs


@pytest.mark.parametrize("V,E,p,s,F", [(120, 500, 4, 4, 16), (80, 200, 2, 5, 8),
                                       (50, 600, 6, 2, 32)])
def test_tile_spmm_sweep(V, E, p, s, F, rng):
    g = graphs.random_graph(V, E, seed=V)
    ts = tiling.grid_tile(g, p, s, sparse=True)
    x = rng.standard_normal((V, F)).astype(np.float32)
    adj, flags = tops.densify_tiles(ts)
    xs = tops.gather_sources(ts, x)
    ref = tile_spmm_ref(jnp.asarray(adj), xs, jnp.asarray(ts.part_id), ts.n_dst_parts)
    out = tops.spmm(jnp.asarray(adj), xs, jnp.asarray(ts.part_id),
                    jnp.asarray(flags), n_parts=ts.n_dst_parts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
    # cross-check against whole-graph segment-sum
    seg = jax.ops.segment_sum(jnp.asarray(x)[g.src], jnp.asarray(g.dst),
                              num_segments=V)
    for pi in range(ts.n_dst_parts):
        n, lo = int(ts.part_size[pi]), int(ts.part_start[pi])
        np.testing.assert_allclose(np.asarray(out)[pi, :n],
                                   np.asarray(seg)[lo:lo + n], atol=1e-4, rtol=1e-4)


def test_tile_spmm_bucketed_matches_global(rng):
    """Per-bucket kernel calls (bucket-aware densify/gather), partition
    outputs summed across buckets == one global-pad kernel call."""
    g = graphs.random_graph(140, 700, seed=9, model="powerlaw")
    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    bt = tiling.bucket_tiles(ts, 3)
    x = rng.standard_normal((g.n_vertices, 16)).astype(np.float32)

    adj, flags = tops.densify_tiles(ts)
    ref = tops.spmm(jnp.asarray(adj), tops.gather_sources(ts, x),
                    jnp.asarray(ts.part_id), jnp.asarray(flags),
                    n_parts=ts.n_dst_parts)

    total = jnp.zeros_like(ref)
    for b, (adj_b, flags_b), xs_b in zip(bt.buckets, tops.densify_tiles(bt),
                                         tops.gather_sources(bt, x)):
        out = tops.spmm(jnp.asarray(adj_b), xs_b, jnp.asarray(b.part_id),
                        jnp.asarray(flags_b), n_parts=b.n_dst_parts)
        present = jnp.asarray(np.isin(np.arange(b.n_dst_parts), b.part_id))
        total = total + jnp.where(present[:, None, None], out, 0.0)
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_segment_softmax_online_vs_ref(rng):
    g = graphs.random_graph(90, 400, seed=7)
    ts = tiling.grid_tile(g, 3, 3, sparse=True)
    F = 12
    x = rng.standard_normal((g.n_vertices, F)).astype(np.float32)
    adj, flags = tops.densify_tiles(ts)
    xs = tops.gather_sources(ts, x)
    scores = np.where(adj > 0, rng.standard_normal(adj.shape).astype(np.float32), -1e30)
    ref = segment_softmax_ref(jnp.asarray(scores), xs, jnp.asarray(ts.part_id),
                              ts.n_dst_parts)
    out = tops.gat_aggregate(jnp.asarray(scores), xs, jnp.asarray(ts.part_id),
                             jnp.asarray(flags), n_parts=ts.n_dst_parts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-4)
