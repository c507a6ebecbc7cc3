"""Property-based optimization-pass conformance (ISSUE 5).

``passes.cse_trace`` and the E2V + DCE pipeline must (a) never increase
node/op counts and (b) preserve program outputs, over randomly generated
stacked traces.  The deterministic companions below run even without
hypothesis (the pinned stacked-GCN dedupe count lives in
``test_multilayer.py::test_cross_layer_cse_removes_ops_on_stacked_gcn``).
"""
import numpy as np
import pytest

from repro.core import compiler, executor, passes, pipeline
from repro.gnn import graphs, models

REL_TOL = 1e-5   # pass round-trips re-run identical float ops


def _rel_err(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / max(1.0, np.max(np.abs(a))))


def _trace(name, n_layers, dim):
    return (models.trace_named(name, dim, dim) if n_layers == 1
            else models.trace_stacked(name, n_layers, dim, dim, dim))


def _check_cse_roundtrip(name, n_layers, dim, seed):
    """cse_trace: fewer-or-equal nodes, identical outputs, idempotent."""
    tr = _trace(name, n_layers, dim)
    tr2, removed = passes.cse_trace(tr)
    assert removed >= 0
    assert len(tr2.nodes) == len(tr.nodes) - removed
    assert len(tr2.nodes) <= len(tr.nodes)
    assert len(tr2.outputs) == len(tr.outputs)
    _, removed_again = passes.cse_trace(tr2)
    assert removed_again == 0          # value numbering converges in one pass

    g = graphs.random_graph(40, 160, seed=seed, model="powerlaw",
                            n_edge_types=3)
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    ref = executor.run_reference(tr, g, inputs, params)
    out = executor.run_reference(tr2, g, inputs, params)
    for a, b in zip(ref, out):
        assert _rel_err(a, b) < REL_TOL


def _check_optimize_roundtrip(name, n_layers, dim, seed):
    """E2V + DCE through compile_gnn: op count never grows, outputs hold."""
    tr = _trace(name, n_layers, dim)
    c = compiler.compile_gnn(tr)
    assert c.ir.op_count() <= c.naive_ir.op_count()
    assert c.opt_report["dce_removed"] >= 0
    g = graphs.random_graph(36, 150, seed=seed, model="powerlaw",
                            n_edge_types=3)
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    ref = executor.run_reference(tr, g, inputs, params)
    from repro.core import tiling
    ts = tiling.grid_tile(g, 3, 3, sparse=True)
    out = pipeline.run_pipelined(c, g, ts, inputs, params,
                                 kernel_dispatch=False)
    for a, b in zip(ref, out):
        assert _rel_err(a, b) < 1e-4


# ---------------------------------------------------------------------------
# deterministic sweep (runs everywhere, hypothesis or not)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", models.PAPER_MODELS)
def test_cse_roundtrip_deterministic(name):
    _check_cse_roundtrip(name, 2, 8, seed=11)


def test_cse_dedupe_count_pin():
    """Regression pin (PR 4): stacked GCN's re-emitted normalized adjacency
    costs exactly 3 deduplicated nodes per extra layer."""
    counts = {L: compiler.compile_gnn(
        models.trace_stacked("gcn", L, 16, 16, 16)).opt_report["cse_removed"]
        for L in (1, 2, 3)}
    assert counts[1] == 0
    assert counts[2] - counts[1] == 3
    assert counts[3] - counts[2] == 3


def test_optimize_roundtrip_deterministic():
    for name in ("gat_naive", "sage_naive"):
        if name in models.MODELS:
            _check_optimize_roundtrip(name, 1, 8, seed=13)
    _check_optimize_roundtrip("sage", 2, 8, seed=13)


# ---------------------------------------------------------------------------
# randomized sweep (hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # pragma: no cover
    pass                                              # deterministic tests above still run
else:
    @pytest.mark.slow
    @settings(max_examples=15, deadline=None)
    @given(name=st.sampled_from(list(models.PAPER_MODELS)),
           n_layers=st.integers(1, 3),
           dim=st.sampled_from([4, 8]),
           seed=st.integers(0, 2**16))
    def test_cse_trace_property(name, n_layers, dim, seed):
        _check_cse_roundtrip(name, n_layers, dim, seed)

    @pytest.mark.slow
    @settings(max_examples=8, deadline=None)
    @given(name=st.sampled_from(["gcn", "gat", "sage", "ggnn", "rgcn"]),
           n_layers=st.integers(1, 2),
           seed=st.integers(0, 2**16))
    def test_optimize_property(name, n_layers, seed):
        _check_optimize_roundtrip(name, n_layers, 8, seed)
