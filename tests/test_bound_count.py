"""The tile grid's edge count, bound at ``bind`` (``core/pipeline.py``).

``PipelinedRunner.bind`` builds the (T, Dmax, Smax) parallel-edge count of
each COO tile batch a kernel block reads it from (a grid weight, a grid
score, pure SpMM's adjacency) with one device scatter
(``tile_spmm.ops.edge_count``), and the forward only reads it.  Pinned
here: the bound count equals the host oracle ``densify_tiles`` exactly, for
every bucket and the softmax batch of bucketed tiles; ``bound_counts``
follows the program and the layout; pure SpMM's adjacency is that same
count; the serving engine neither recompiles for it nor counts it as a
host-to-device copy.
"""
import jax
import numpy as np
import pytest

from repro.core import compiler, executor, pipeline, tiling
from repro.gnn import graphs, models
from repro.kernels.tile_spmm import ops as tops
from repro.serve import InferenceServer

DIM = 16


def _graph():
    """Power-law digraph with 20 parallel edges."""
    g = graphs.random_graph(90, 400, seed=3, model="powerlaw")
    g = graphs.Graph(src=np.concatenate([g.src, g.src[:20]]),
                     dst=np.concatenate([g.dst, g.dst[:20]]), n_vertices=90)
    assert len(set(zip(g.src.tolist(), g.dst.tolist()))) < g.n_edges
    return g


def _runner(model, g, layout="coo", n_buckets=None):
    tiles, _ = tiling.build_tiles(g, 3, 3, layout=layout, n_buckets=n_buckets)
    tr = models.trace_stacked(model, 2, DIM, DIM, DIM)
    r = pipeline.PipelinedRunner(compiler.compile_gnn(tr), g, tiles,
                                 kernel_dispatch=True)
    return r, tiles, tr


def _counts(ops):
    """The (T, Dmax, Smax) edge counts among bound operands: per bucket, and
    of the softmax batch (``None`` where none was bound)."""
    _, kcs, _, kc0, _, _ = ops
    return [kc.get("cnt") for kc in kcs], (None if kc0 is None
                                           else kc0.get("cnt"))


@pytest.mark.parametrize("model,n_buckets,per_bucket,source", [
    ("gcn", None, True, False),
    ("gat", None, True, False),      # the softmax batch is bucket 0
    ("gin", None, True, False),      # pure SpMM
    ("gcn", 2, True, False),
    ("gat", 2, False, True),
    ("gin", 2, True, False),
])
def test_bound_count_equals_the_host_oracle(model, n_buckets, per_bucket,
                                            source):
    r, tiles, _ = _runner(model, _graph(), n_buckets=n_buckets)
    ops = r.bind(tiles)
    buckets = tiles.buckets if n_buckets else [tiles]
    cnts, cnt0 = _counts(ops)
    assert r.bound_counts == per_bucket * len(buckets) + source
    for b, cnt in zip(buckets, cnts):
        if not per_bucket:
            assert cnt is None
            continue
        oracle = tops.densify_tiles(b)[0]
        assert cnt.dtype == np.float32 and oracle.max() > 1   # parallel edges
        np.testing.assert_array_equal(np.asarray(cnt), oracle)
    if source:
        np.testing.assert_array_equal(np.asarray(cnt0),
                                      tops.densify_tiles(tiles.source)[0])
    else:
        assert cnt0 is None


@pytest.mark.parametrize("model,layout", [
    ("gcn", "csr"), ("gat", "csr"), ("gin", "csr"), ("rgcn", "coo")])
def test_no_count_where_no_block_reads_one(model, layout):
    g = graphs.random_graph(90, 400, seed=3, model="powerlaw",
                            n_edge_types=3 if model == "rgcn" else None)
    r, tiles, _ = _runner(model, g, layout=layout)
    cnts, cnt0 = _counts(r.bind(tiles))
    assert r.bound_counts == 0
    assert cnts == [None] and cnt0 is None


def test_pure_spmm_adjacency_is_the_bound_count():
    """GIN's sum aggregation reads the bound count as its adjacency: one
    count per tile batch, no second copy, and doubling it gives the
    reference on the graph with every edge twice."""
    g = _graph()
    r, tiles, tr = _runner("gin", g)
    ops = r.bind(tiles)
    kc = ops[1][0]
    assert set(kc) == {"flags", "pmask", "cnt"}
    grid = (tiles.n_tiles, int(tiles.part_size.max()), tiles.s_max)
    assert [a.shape for a in jax.tree_util.tree_leaves(ops)
            if a.shape == grid] == [grid]

    params, inputs = models.init_params(tr, 1), models.init_inputs(tr, g, 1)
    doubled = (ops[0], ({**kc, "cnt": 2 * kc["cnt"]},)) + ops[2:]
    out = np.asarray(r(inputs, params, doubled)[0])
    g2 = graphs.Graph(src=np.concatenate([g.src, g.src]),
                      dst=np.concatenate([g.dst, g.dst]),
                      n_vertices=g.n_vertices)
    ref = np.asarray(executor.run_reference(tr, g2, inputs, params)[0])
    assert np.max(np.abs(out - ref)) / np.max(np.abs(ref)) <= 1e-5


def _request(tr, seed):
    gs = [graphs.random_graph(48, 200, seed=seed + k, model="powerlaw")
          for k in range(4)]
    return gs, [models.init_inputs(tr, g, seed=seed + k)
                for k, g in enumerate(gs)]


@pytest.mark.parametrize("model", ["gcn", "gat"])
def test_serving_binds_the_count_without_recompiling(model):
    """Two requests of one size class: the second compiles nothing, neither
    the runner's forward nor the count builder, and answers as the
    oracle does."""
    tr = models.trace_named(model, DIM, DIM)
    params = models.init_params(tr)
    server = InferenceServer(compiler.compile_gnn(tr), params)
    server.submit(*_request(tr, 0))                   # warm-up
    runner = next(iter(server.cache._entries.values()))
    assert runner.bound_counts == 1
    sizes = runner.jit_cache_size(), tops.edge_count._cache_size()
    assert sizes[0] == 1 and sizes[1] >= 1

    gs, ins = _request(tr, 100)
    outs = server.submit(gs, ins)
    assert server.compile_count == 1 and server.cache.stats.hits == 1
    assert (runner.jit_cache_size(), tops.edge_count._cache_size()) == sizes
    for g, inp, out in zip(gs, ins, outs):
        ref = executor.run_reference(tr, g, inp, params)
        for r, o in zip(ref, out):
            r = np.asarray(r)
            assert np.max(np.abs(o - r)) <= 1e-5 * max(np.max(np.abs(r)), 1)
