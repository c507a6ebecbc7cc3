"""Conformance of the single-chip engine: ``PipelinedRunner`` ≡ the
whole-graph oracle across every option it takes.

One matrix: each model × edge layout (COO / CSR within a tile) × vertex
order (identity / degree) × dispatch mode (scan / Pallas kernels) × tile
batch (one global pad / size buckets).  The graph is power-law with
destinations that have no in-edge, so a max-reduce over an empty
neighbourhood (sage) stays in view.  The runner takes inputs in original
vertex ids and returns outputs in them; the oracle runs on the original
graph.
"""
import jax.numpy as jnp
import pytest

from repro.core import compiler, executor, pipeline, reorder, tiling
from repro.gnn import graphs, models

TOL = 5e-4

ORDERS = {"identity": reorder.identity_order, "degree": reorder.degree_sort}


@pytest.mark.parametrize("bucketed", [False, True], ids=["plain", "bucketed"])
@pytest.mark.parametrize("kernel_dispatch", [False, True], ids=["scan", "kernel"])
@pytest.mark.parametrize("order", list(ORDERS))
@pytest.mark.parametrize("layout", ["coo", "csr"])
@pytest.mark.parametrize("name", models.PAPER_MODELS + ("gin",))
def test_runner_matches_oracle(name, layout, order, kernel_dispatch, bucketed):
    g = graphs.random_graph(180, 750, seed=3, model="powerlaw", n_edge_types=3)
    assert (g.in_degrees() == 0).any()
    tr = models.trace_named(name, 24, 24)
    c = compiler.compile_gnn(tr)
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    ref = executor.run_reference(tr, g, inputs, params)

    r = ORDERS[order](g)
    ts = tiling.grid_tile(r.graph, 4, 4, sparse=True, layout=layout)
    if bucketed:
        ts = tiling.bucket_tiles(ts, 3)
    out = pipeline.run_pipelined(
        c, r.graph, ts, inputs, params, kernel_dispatch=kernel_dispatch, reordering=r
    )
    assert len(out) == len(ref)
    for a, b in zip(ref, out):
        assert float(jnp.max(jnp.abs(a - b))) < TOL
