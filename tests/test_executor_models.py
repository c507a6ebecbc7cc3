"""Integration: compiled+tiled+pipelined execution ≡ whole-graph oracle for
the paper models and gin, across tiling strategies (vertex order and edge
layout: ``test_runner_conformance.py``)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiler, executor, pipeline, tiling
from repro.gnn import graphs, models
from repro.kernels.tile_spmm import ops as tops

TOL = 5e-4


def _run_all(name, g, strategy):
    tr = models.trace_named(name, 24, 24)
    c = compiler.compile_gnn(tr)
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    ref = executor.run_reference(tr, g, inputs, params)
    tile_kernel = None
    if strategy == "regular":
        ts = tiling.grid_tile(g, 4, 4, sparse=False)
    else:
        ts = tiling.grid_tile(g, 4, 4, sparse=True)
    if strategy in ("bucketed", "bucketed+kernel"):
        ts = tiling.bucket_tiles(ts, 3)
    if strategy == "bucketed+kernel":
        tile_kernel = tops.spmm
    if strategy in ("regular", "sparse"):
        out_kernel = pipeline.run_pipelined(c, g, ts, inputs, params,
                                            kernel_dispatch=True)
        for a, b in zip(ref, out_kernel):
            assert float(jnp.max(jnp.abs(a - b))) < TOL, "kernel != oracle"
    out_pipe = pipeline.run_pipelined(c, g, ts, inputs, params,
                                      tile_kernel=tile_kernel)
    for a, b in zip(ref, out_pipe):
        assert float(jnp.max(jnp.abs(a - b))) < TOL, "pipelined != oracle"


@pytest.mark.parametrize("name", models.PAPER_MODELS + ("gin",))
@pytest.mark.parametrize("strategy", ["regular", "sparse", "bucketed",
                                      "bucketed+kernel"])
def test_tiled_matches_oracle(name, strategy):
    g = graphs.random_graph(220, 900, seed=1, model="powerlaw", n_edge_types=3)
    _run_all(name, g, strategy)


def test_kernel_engages_on_pure_spmm_models():
    """The scheduler pass must tag pure sum-gather phases ``pallas_spmm``
    so the Pallas inner body replaces the scan."""
    from repro.core import schedule
    for name, engaged in [("gcn", True), ("ggnn", True), ("gin", True),
                          ("rgcn", False), ("sage", False)]:
        c = compiler.compile_gnn(models.trace_named(name, 16, 16))
        kernels = {k for ks in c.schedule(True).kernels_by_level().values()
                   for k in ks}
        assert (schedule.KERNEL_SPMM in kernels) == engaged, name


def test_empty_partition_handled():
    # a graph whose high partitions have no in-edges
    src = np.array([0, 1, 2, 3], np.int32)
    dst = np.array([0, 0, 1, 1], np.int32)
    g = graphs.Graph(src=src, dst=dst, n_vertices=64, name="skew")
    tr = models.trace_named("gcn", 8, 8)
    c = compiler.compile_gnn(tr)
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    ts = tiling.grid_tile(g, 4, 4)
    ref = executor.run_reference(tr, g, inputs, params)
    out = pipeline.run_pipelined(c, g, ts, inputs, params,
                                 kernel_dispatch=True)
    assert float(jnp.max(jnp.abs(ref[0] - out[0]))) < TOL


def test_pipelined_uses_single_jit():
    g = graphs.random_graph(100, 400, seed=0)
    tr = models.trace_named("gcn", 8, 8)
    c = compiler.compile_gnn(tr)
    runner = pipeline.PipelinedRunner(c, g, tiling.grid_tile(g, 2, 2))
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    o1 = runner(inputs, params)
    o2 = runner(inputs, params)  # second call hits the jit cache
    assert float(jnp.max(jnp.abs(o1[0] - o2[0]))) == 0.0
