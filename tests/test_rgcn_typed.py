"""R-GCN's typed aggregation (Schlichtkrull et al., eq. 2, block-diagonal
weights) through the normal path: ``compile_gnn`` ->
``PipelinedRunner(kernel_dispatch=True)`` on the relation kernel, and the
oracle ``run_reference``, each against a plain float64 reference written
from the equation.

The graph holds every case the relation-grouped layout must keep apart:
parallel edges of different relations between one pair (and of one
relation, so ``c_{i,r} > 1`` on them), an isolated vertex, and a relation
with no edges.

Tolerance: every engine runs in float32 and the reference in float64; a
layer sums at most a few hundred products of unit-scale values, so float32
rounding stays near 1e-6 of the output's scale, and 2e-5 of the largest
reference value leaves room for two layers' accumulation orders.
"""
import numpy as np
import pytest

from repro.core import (compiler, executor, pipeline, schedule, tiling)
from repro.core.analysis import verify_schedule
from repro.gnn import graphs, models

REL_TOL = 2e-5
N_TYPES = 12              # relation 11 has no edge


def _typed_graph(seed=0, n=300, e=2000):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n - 1, e)           # vertex n-1 stays isolated
    dst = rng.integers(0, n - 1, e)
    et = rng.integers(0, N_TYPES - 1, e)
    # one pair carries relations 0, 3 and 3 again: three parallel edges
    src[:3], dst[:3], et[:3] = 5, 7, [0, 3, 3]
    keep = src != dst
    return graphs.Graph(src=src[keep].astype(np.int32),
                        dst=dst[keep].astype(np.int32), n_vertices=n,
                        edge_type=et[keep].astype(np.int32), name="typed")


def _plain(g, params, x, n_layers):
    """The layer equation in float64, one edge at a time."""
    x = np.asarray(x, np.float64)
    c = {}
    for d, r in zip(g.dst, g.edge_type):
        c[(d, r)] = c.get((d, r), 0) + 1
    for l in range(n_layers):
        w = np.asarray(params[f"l{l}.W_rel"], np.float64)
        _, nb, k, m = w.shape
        h = x @ np.asarray(params[f"l{l}.W_self"], np.float64)
        for s, d, r in zip(g.src, g.dst, g.edge_type):
            blocks = x[s].reshape(nb, k)
            msg = np.einsum("bk,bkm->bm", blocks, w[r]).reshape(-1)
            h[d] += msg / c[(d, r)]
        x = np.maximum(h, 0.0) if l < n_layers - 1 else h
    return x


def _rel_err(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / np.max(np.abs(want)))


CASES = [(2, 20, 4), (1, 20, 4), (2, 16, 1)]   # (layers, width, blocks)


@pytest.mark.parametrize("n_layers,dim,n_blocks", CASES)
@pytest.mark.parametrize("bucketed", [False, True], ids=["tiles", "buckets"])
def test_runner_and_oracle_match_the_equation(n_layers, dim, n_blocks,
                                              bucketed):
    g = _typed_graph()
    tr = models.trace_stacked("rgcn", n_layers, dim, dim, dim,
                              n_types=N_TYPES, n_blocks=n_blocks)
    c = compiler.compile_gnn(tr)
    params = models.init_params(tr, seed=1)
    inputs = models.init_inputs(tr, g, seed=2)
    want = _plain(g, params, inputs["x"], n_layers)

    ref = executor.run_reference(tr, g, inputs, params)
    assert _rel_err(ref[0], want) < REL_TOL

    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    tiles = tiling.bucket_tiles(ts, 3) if bucketed else ts
    runner = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    assert runner._kernels == {schedule.KERNEL_RELATION}
    out = runner(inputs, params)
    assert _rel_err(out[0], want) < REL_TOL
    rows = runner.relation_rows
    assert rows["real_rows"] == g.n_edges
    assert rows["relation_groups"] == N_TYPES - 1


def test_published_sizes_schedule_no_scan_gather():
    """The FB15k-237 encoder (474 relations, 100 blocks of 5x5, width 500,
    two layers) puts every gather on the relation kernel; the lint names
    no missed kernel."""
    tr = models.trace_stacked("rgcn", 2, 500, 500, 500, n_types=474,
                              n_blocks=100)
    assert tr.params["l0.W_rel"] == (474, 100, 5, 5)
    assert models.n_edge_types(tr) == 474
    sp = compiler.compile_gnn(tr).schedule(True)
    tags = [g.kernel for ph in sp.phases for g in ph.gathers]
    assert tags == [schedule.KERNEL_RELATION] * 2
    diags = verify_schedule(sp)
    assert not [d for d in diags if d.code in ("ZS110", "ZS112")]


def test_relation_layout_keeps_parallel_typed_edges_apart():
    g = _typed_graph()
    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    lay = tiling.relation_layout(ts, N_TYPES)
    K = tiling.RELATION_BLOCK_ROWS
    cap_blocks, cap_tiles = tiling.relation_capacity(ts, N_TYPES)
    assert lay.n_slots == K * cap_blocks
    real = lay.slot_dst < g.n_vertices
    assert int(real.sum()) == lay.n_real == g.n_edges
    # every edge once, each block of one relation
    assert sorted(lay.slot_gid[real].tolist()) == list(range(g.n_edges))
    rel_of_slot = np.repeat(lay.block_rel, K)
    np.testing.assert_array_equal(rel_of_slot[real],
                                  g.edge_type[lay.slot_gid[real]])
    # the three parallel 5 -> 7 edges are three rows
    pair = real & (lay.slot_src == 5) & (lay.slot_dst == 7)
    assert sorted(rel_of_slot[pair].tolist()) == [0, 3, 3]
    # destination tiles: every real row once, in destination order, each
    # tile within one partition of K destinations, partitions in order
    assert lay.sum_dst.shape == (cap_tiles, 1, K)
    d = lay.sum_dst.reshape(-1)
    rows = lay.sum_src[d >= 0]
    assert sorted(rows.tolist()) == sorted(np.flatnonzero(real).tolist())
    glob = (np.repeat(lay.sum_part, K) * K + d)[d >= 0]
    np.testing.assert_array_equal(glob, lay.slot_dst[rows])
    assert np.all(np.diff(glob) >= 0) and np.all(np.diff(lay.sum_part) >= 0)
    assert set(lay.sum_part) == set(range(-(-g.n_vertices // K)))
    assert lay.counts() == dict(real_rows=g.n_edges,
                                padded_rows=lay.n_slots - g.n_edges,
                                relation_groups=N_TYPES - 1,
                                sum_padded_rows=cap_tiles * K - g.n_edges)


def test_typed_transform_chunks_agree(monkeypatch):
    """The oracle's chunked typed transform gives the per-row products
    whatever the chunk size (a chunk of 7 rows here, 3 chunks padded)."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((17, 20)).astype(np.float32)
    w = rng.standard_normal((5, 4, 5, 3)).astype(np.float32)
    et = rng.integers(0, 5, 17)
    want = np.einsum("ebk,ebkm->ebm", x.reshape(17, 4, 5).astype(np.float64),
                     w[et].astype(np.float64)).reshape(17, 12)
    monkeypatch.setattr(executor, "_TYPED_CHUNK_ELEMS", 7 * 4 * 5 * 3)
    got = executor.typed_transform(x, et, w)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
