"""Vertex-only edge weights and scores on the dense tile grid
(``core/pipeline.py``).

A weighted-SpMM block whose edge weight reads only the values at each
edge's two endpoints (stacked GCN's ``dn[src]·dn[dst]``) is evaluated once
per tile on the dense ``(Dmax, Smax)`` grid and scaled by the tile's
edge-count adjacency, instead of per padded edge slot.  A segment-softmax
block whose score is vertex-only (GAT's ``leaky_relu(es[src] + ed[dst])``)
is evaluated on the same grid, shifted by ``log`` of the edge count and fed
to the kernel with per-source-row values.  Pinned here: both grid paths
match the whole-graph reference and the per-slot path, stay finite where
off-edge grid cells are not, engage exactly where the weight or score is
vertex-only, leave no per-slot gather in the ``zipper.edge`` stage, and
share one edge count that ``bind`` scattered: the forward scatters none.
"""
import re

import jax
import numpy as np
import pytest

from repro.core import compiler, executor, pipeline, tiling
from repro.core.trace import trace_model
from repro.gnn import graphs, models

DIM = 16
REL_TOL = 1e-6


def _graph(n_isolated=7):
    """Power-law digraph with parallel edges, plus ``n_isolated`` vertices
    at the top of the id range that no edge touches."""
    g = graphs.random_graph(90, 400, seed=3, model="powerlaw")
    src = np.concatenate([g.src, g.src[:20]])        # 20 more parallel edges
    dst = np.concatenate([g.dst, g.dst[:20]])
    g = graphs.Graph(src=src, dst=dst, n_vertices=90 + n_isolated)
    assert len(set(zip(g.src.tolist(), g.dst.tolist()))) < g.n_edges
    assert np.sum(g.in_degrees() + g.out_degrees() == 0) >= n_isolated
    return g


def _tiles(g, layout="coo", n_buckets=None):
    tiles, _ = tiling.build_tiles(g, 3, 3, layout=layout, n_buckets=n_buckets)
    return tiles


def _rel(out, ref):
    out, ref = np.asarray(out), np.asarray(ref)
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


def _gcn():
    tr = models.trace_stacked("gcn", 2, DIM, DIM, DIM)
    return tr, compiler.compile_gnn(tr)


def _div_model():
    """One layer whose weight divides by the destination's value:
    ``out = Σ_e h[src] · a[src] / b[dst]``."""
    def build(tr, g):
        x = tr.input_vertex(DIM, "x")
        a = tr.input_vertex(1, "a")
        b = tr.input_vertex(1, "b")
        h = x.matmul(tr.param("W", (DIM, DIM)))
        w = g.scatter_src(a) / g.scatter_dst(b)
        tr.mark_output(g.gather_sum(g.scatter_src(h) * w))
    return trace_model(build, name="div_weight")


def _edge_input_model(direct=False):
    """One layer whose weight reads an edge input: ``dn[src] · ew[e]``, or
    with ``direct`` the edge input ``ew[e]`` itself."""
    def build(tr, g):
        x = tr.input_vertex(DIM, "x")
        dn = tr.input_vertex(1, "dnorm")
        ew = tr.input_edge(1, "ew")
        h = x.matmul(tr.param("W", (DIM, DIM)))
        w = ew if direct else g.scatter_src(dn) * ew
        tr.mark_output(g.gather_sum(g.scatter_src(h) * w))
    return trace_model(build, name="edge_weight")


@pytest.mark.parametrize("n_buckets", [None, 2], ids=["plain", "bucketed"])
def test_grid_weights_match_reference_and_per_slot(n_buckets, monkeypatch):
    g = _graph()
    tiles = _tiles(g, n_buckets=n_buckets)
    assert isinstance(tiles, tiling.BucketedTileSet) == (n_buckets is not None)
    tr, c = _gcn()
    params, inputs = models.init_params(tr, 1), models.init_inputs(tr, g, 1)
    ref = executor.run_reference(tr, g, inputs, params)[0]

    grid = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    assert grid.weight_paths == {"grid": 2, "edge": 0}
    out_grid = grid(inputs, params)[0]

    monkeypatch.setattr(pipeline, "_vertex_only", lambda blk: False)
    slot = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    assert slot.weight_paths == {"grid": 0, "edge": 2}
    out_slot = slot(inputs, params)[0]

    assert _rel(out_grid, ref) <= REL_TOL
    assert _rel(out_grid, out_slot) <= REL_TOL


def test_grid_weight_division_by_zero_stays_finite():
    """``b`` is 0 on the isolated vertices, the last of which is also what
    padded destination rows read: their grid cells are ±inf, and a bare
    ``cnt * grid`` would turn them into NaN rows of the output."""
    g = _graph()
    tr = _div_model()
    c = compiler.compile_gnn(tr)
    tiles = _tiles(g)
    runner = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    assert runner.weight_paths == {"grid": 1, "edge": 0}

    rng = np.random.default_rng(5)
    V = g.n_vertices
    b = rng.uniform(0.5, 2.0, (V, 1)).astype(np.float32)
    b[g.in_degrees() == 0] = 0.0
    assert b[V - 1, 0] == 0.0 and int(tiles.part_size.max()) * 3 > V
    inputs = {"x": rng.standard_normal((V, DIM)).astype(np.float32),
              "a": rng.standard_normal((V, 1)).astype(np.float32), "b": b}
    params = {"W": rng.standard_normal((DIM, DIM)).astype(np.float32)}

    out = np.asarray(runner(inputs, params)[0])
    ref = np.asarray(executor.run_reference(tr, g, inputs, params)[0])
    assert np.all(np.isfinite(ref))
    assert np.all(np.isfinite(out))
    assert _rel(out, ref) <= REL_TOL


@pytest.mark.parametrize("case,expected", [
    ("gcn-coo", {"grid": 2, "edge": 0}),
    ("gcn-csr", {"grid": 0, "edge": 2}),
    ("edge-input", {"grid": 0, "edge": 1}),
    ("edge-input-weight", {"grid": 0, "edge": 1}),
    ("gat-coo", {"grid": 0, "edge": 0}),
])
def test_weight_paths_follow_the_program(case, expected):
    g = _graph()
    if case.startswith("edge-input"):
        tr = _edge_input_model(direct=case.endswith("weight"))
    else:
        tr = models.trace_stacked(case.split("-")[0], 2, DIM, DIM, DIM)
    tiles = _tiles(g, layout="csr" if case.endswith("csr") else "coo")
    runner = pipeline.PipelinedRunner(compiler.compile_gnn(tr), g, tiles,
                                      kernel_dispatch=True)
    assert runner.weight_paths == expected
    if case.startswith("edge-input"):
        # the per-slot path still serves it, against the reference
        inputs = models.init_inputs(tr, g, 2)
        inputs["dnorm"] = np.abs(inputs["dnorm"])
        params = models.init_params(tr, 2)
        ref = executor.run_reference(tr, g, inputs, params)[0]
        assert _rel(runner(inputs, params)[0], ref) <= REL_TOL


def _edge_gather_sizes(runner, inputs, params):
    """Element counts of every gather the ``zipper.edge`` stage lowers to."""
    hlo = runner.lower(inputs, params).as_text(dialect="hlo", debug_info=True)
    sizes = []
    for ln in hlo.splitlines():
        m = re.search(r"= f32\[([\d,]*)\][^ ]* gather\(", ln)
        if m and "zipper.edge" in ln:
            sizes.append(int(np.prod([int(d) for d in m.group(1).split(",")
                                      if d])))
    return sizes


def test_no_per_slot_gather_in_the_edge_stage(monkeypatch):
    g = _graph()
    tiles = _tiles(g)
    T, emax = tiles.edge_src.shape
    assert emax not in (tiles.s_max, int(tiles.part_size.max()))
    tr, c = _gcn()
    params, inputs = models.init_params(tr, 1), models.init_inputs(tr, g, 1)

    grid = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    sizes = _edge_gather_sizes(grid, inputs, params)
    assert sizes and T * emax not in sizes

    # the check bites: the per-slot path gathers one scalar per edge slot
    monkeypatch.setattr(pipeline, "_vertex_only", lambda blk: False)
    slot = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    assert T * emax in _edge_gather_sizes(slot, inputs, params)


# ---- segment-softmax scores on the grid ------------------------------------

def _gat():
    tr = models.trace_stacked("gat", 2, DIM, DIM, DIM)
    return tr, compiler.compile_gnn(tr)


def _div_score_model():
    """One softmax layer whose score divides by the destination's value:
    ``out = Σ_e softmax_e(a[src] / b[dst]) · h[src]``."""
    def build(tr, g):
        x = tr.input_vertex(DIM, "x")
        a = tr.input_vertex(1, "a")
        b = tr.input_vertex(1, "b")
        h = x.matmul(tr.param("W", (DIM, DIM)))
        alpha = g.edge_softmax(g.scatter_src(a) / g.scatter_dst(b))
        tr.mark_output(g.gather_sum(g.scatter_src(h) * alpha))
    return trace_model(build, name="div_score")


@pytest.mark.parametrize("n_buckets", [None, 2], ids=["plain", "bucketed"])
def test_grid_scores_match_reference_and_per_slot(n_buckets, monkeypatch):
    g = _graph()
    tiles = _tiles(g, n_buckets=n_buckets)
    tr, c = _gat()
    params, inputs = models.init_params(tr, 1), models.init_inputs(tr, g, 1)
    ref = executor.run_reference(tr, g, inputs, params)[0]

    grid = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    assert grid.score_paths == {"grid": 2, "edge": 0}
    out_grid = np.asarray(grid(inputs, params)[0])

    monkeypatch.setattr(pipeline, "_vertex_only", lambda blk: False)
    slot = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    assert slot.score_paths == {"grid": 0, "edge": 2}
    out_slot = slot(inputs, params)[0]

    assert np.all(np.isfinite(out_grid))
    # destinations with no in-edge (the isolated vertices among them) read 0
    isolated = g.in_degrees() == 0
    assert np.sum(isolated) >= 7 and np.all(out_grid[isolated] == 0.0)
    assert _rel(out_grid, ref) <= REL_TOL
    assert _rel(out_grid, out_slot) <= REL_TOL


def test_grid_score_division_by_zero_stays_finite():
    """``b`` is 0 on the isolated vertices and on what padded destination
    rows read: their grid scores are ±inf or NaN, which only a select keeps
    out of the kernel's softmax."""
    g = _graph()
    tr = _div_score_model()
    tiles = _tiles(g)
    runner = pipeline.PipelinedRunner(compiler.compile_gnn(tr), g, tiles,
                                      kernel_dispatch=True)
    assert runner.score_paths == {"grid": 1, "edge": 0}

    rng = np.random.default_rng(6)
    V = g.n_vertices
    b = rng.uniform(0.5, 2.0, (V, 1)).astype(np.float32)
    b[g.in_degrees() == 0] = 0.0
    assert b[V - 1, 0] == 0.0 and int(tiles.part_size.max()) * 3 > V
    inputs = {"x": rng.standard_normal((V, DIM)).astype(np.float32),
              "a": rng.standard_normal((V, 1)).astype(np.float32), "b": b}
    params = {"W": rng.standard_normal((DIM, DIM)).astype(np.float32)}

    out = np.asarray(runner(inputs, params)[0])
    ref = np.asarray(executor.run_reference(tr, g, inputs, params)[0])
    assert np.all(np.isfinite(ref))
    assert np.all(np.isfinite(out))
    assert _rel(out, ref) <= REL_TOL


def _gat_naive(optimize):
    tr = trace_model(lambda t, gr: models.build_gat_naive(t, gr, DIM, DIM),
                     name="gat_naive")
    return tr, compiler.compile_gnn(tr, optimize=optimize)


@pytest.mark.parametrize("case,expected", [
    ("gat-coo", {"grid": 2, "edge": 0}),
    ("gat-csr", {"grid": 0, "edge": 2}),
    # the compiler hoists gat_naive's attention mat-vecs to the vertices
    ("gat_naive-coo", {"grid": 1, "edge": 0}),
    # unoptimized, its score holds the per-edge gemv with a parameter
    ("gat_naive-unoptimized-coo", {"grid": 0, "edge": 1}),
    ("gcn-coo", {"grid": 0, "edge": 0}),
])
def test_score_paths_follow_the_program(case, expected):
    g = _graph()
    if case.startswith("gat_naive"):
        tr, c = _gat_naive(optimize="unoptimized" not in case)
    else:
        tr = models.trace_stacked(case.split("-")[0], 2, DIM, DIM, DIM)
        c = compiler.compile_gnn(tr)
    tiles = _tiles(g, layout="csr" if case.endswith("csr") else "coo")
    runner = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    assert runner.score_paths == expected
    if case.startswith("gat_naive"):
        params, inputs = models.init_params(tr, 2), models.init_inputs(tr, g, 2)
        ref = executor.run_reference(tr, g, inputs, params)[0]
        assert _rel(runner(inputs, params)[0], ref) <= REL_TOL


def _shapes(jaxpr):
    """Every (primitive, output shape) in a jaxpr and its sub-jaxprs, once
    per call site."""
    out = []
    for eqn in jaxpr.eqns:
        out += [(eqn.primitive.name, tuple(v.aval.shape))
                for v in eqn.outvars if hasattr(v.aval, "shape")]
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    out += _shapes(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    out += _shapes(sub)
    return out


def _program_shapes(runner, inputs, params):
    args = runner._args(inputs, params, None)
    return _shapes(jax.make_jaxpr(runner._run)(*args).jaxpr)


@pytest.mark.parametrize("n_buckets", [None, 2], ids=["plain", "bucketed"])
def test_no_per_slot_work_in_the_gat_program(n_buckets, monkeypatch):
    g = _graph()
    tiles = _tiles(g, n_buckets=n_buckets)
    st = tiles.source if n_buckets else tiles
    T, emax = st.edge_src.shape
    dmax = int(st.part_size.max())
    assert emax not in (st.s_max, dmax, DIM)
    per_slot = {T * emax, T * emax * DIM}      # a scalar or a row per slot
    tr, c = _gat()
    params, inputs = models.init_params(tr, 1), models.init_inputs(tr, g, 1)

    grid = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    sizes = _edge_gather_sizes(grid, inputs, params)
    assert sizes and not per_slot & set(sizes)
    assert (T, dmax, emax) not in {s for _, s in
                                   _program_shapes(grid, inputs, params)}

    # the checks bite: the per-slot path gathers per edge slot and builds
    # one kernel column per slot
    monkeypatch.setattr(pipeline, "_vertex_only", lambda blk: False)
    slot = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    assert per_slot <= set(_edge_gather_sizes(slot, inputs, params))
    assert (T, dmax, emax) in {s for _, s in
                               _program_shapes(slot, inputs, params)}


@pytest.mark.parametrize("model", ["gat", "gcn"])
def test_one_count_scatter_per_forward(model):
    """Both layers' grid blocks read one edge count, scattered once per
    ``bind`` and handed to the compiled forward as one operand: the forward
    itself scatters nothing over the tile grid."""
    g = _graph()
    tiles = _tiles(g)
    tr, c = _gat() if model == "gat" else _gcn()
    params, inputs = models.init_params(tr, 1), models.init_inputs(tr, g, 1)
    runner = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    assert runner.score_paths["grid"] + runner.weight_paths["grid"] == 2
    T, dmax = tiles.edge_src.shape[0], int(tiles.part_size.max())
    grid = f"f32[{T},{dmax},{tiles.s_max}]"
    hlo = runner._jitted.lower(*runner._args(inputs, params, None)) \
        .compile().as_text()
    assert runner.bound_counts == 1
    assert not [ln for ln in hlo.splitlines()
                if " scatter(" in ln and f"= {grid}" in ln]
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    assert len([ln for ln in entry.splitlines()
                if " parameter(" in ln and f"= {grid}" in ln]) == 1
