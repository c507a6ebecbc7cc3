"""Scheduler-layer tests: one lowering, every engine interprets it.

The :mod:`repro.core.schedule` pass is the single source of truth for phase
structure and kernel-block dispatch; the JAX engines interpret it, and the
ISA codegen costs it.  These tests pin (a) the kernel tags the pattern
matcher must pick, (b) kernel-dispatched blocks against the whole-graph
oracle, and (c) that the engines really do contain no level/role derivation
of their own.  The engine × option parity matrix against the oracle is
``test_runner_conformance.py``.
"""
import inspect

import jax.numpy as jnp
import pytest

from repro.core import compiler, executor, pipeline, schedule, tiling
from repro.gnn import graphs, models

TOL = 5e-4


def _compiled(name, dim=24):
    tr = models.trace_named(name, dim, dim)
    c = compiler.compile_gnn(tr)
    return tr, c


def test_gcn_aggregation_selects_pallas_spmm():
    _, c = _compiled("gcn")
    sp = c.schedule(True)
    assert sp.gather_kernel(0) == schedule.KERNEL_SPMM
    # and the block knows which vertex value feeds the kernel's X operand
    (g,) = sp.phases[0].gathers
    assert g.src_value_id is not None and g.acc.kind == "sum"


@pytest.mark.parametrize("name", ["gat", "gat_naive"])
def test_gat_softmax_selects_pallas_segment_softmax(name):
    """The three-level softmax motif fuses into ONE online-softmax block."""
    _, c = _compiled(name)
    sp = c.schedule(True)
    assert sp.gather_kernel(0) == schedule.KERNEL_SEGMENT_SOFTMAX
    (g,) = sp.phases[0].gathers
    assert g.fused_levels == (0, 1, 2)
    # the fused block subsumes the intermediate gathers: no other gather
    # blocks and no leftover edge work anywhere in the program
    for phase in sp.phases[1:]:
        assert not phase.gathers and not phase.edge.nodes


def test_scan_lowering_has_no_kernel_blocks():
    for name in models.PAPER_MODELS:
        _, c = _compiled(name)
        sp = c.schedule(False)
        kernels = {k for ks in sp.kernels_by_level().values() for k in ks}
        assert kernels <= {schedule.KERNEL_SCAN}, name


def test_gat_fused_softmax_matches_reference_tightly():
    """Acceptance: GAT's edge softmax executes through the Pallas
    segment-softmax block with outputs matching run_reference to 1e-4."""
    g = graphs.random_graph(150, 650, seed=11, model="powerlaw")
    tr, c = _compiled("gat", dim=16)
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    ref = executor.run_reference(tr, g, inputs, params)
    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    assert c.schedule(True).gather_kernel(0) == schedule.KERNEL_SEGMENT_SOFTMAX
    out = pipeline.run_pipelined(c, g, ts, inputs, params,
                                 kernel_dispatch=True)
    assert float(jnp.max(jnp.abs(ref[0] - out[0]))) < 1e-4


def test_engines_have_no_phase_derivation():
    """Acceptance: no engine consults plan.level / plan.role — block
    membership comes entirely from schedule.lower."""
    for mod in (executor, pipeline):
        src = inspect.getsource(mod)
        assert "plan.level" not in src and "plan.role" not in src, mod.__name__
        assert ".level[" not in src and ".role[" not in src, mod.__name__


def test_isa_costs_kernel_blocks():
    """emit_sde consumes the same blocks: the kernel-dispatched program emits
    fused kernel instructions, the scan program the SCTR/GTHR pairs."""
    from repro.core import isa

    _, c = _compiled("gcn")
    e_scan = [i.opcode for i in isa.emit_sde(c.schedule(False)).e.get(0, [])]
    e_ker = [i.opcode for i in isa.emit_sde(c.schedule(True)).e.get(0, [])]
    assert "SCTR.OUTE" in e_scan and "GTHR.DST.SUM" in e_scan
    assert e_ker == ["SPMM.TILE"]

    _, cg = _compiled("gat")
    sde = isa.emit_sde(cg.schedule(True))
    e0 = [i.opcode for i in sde.e.get(0, [])]
    assert "SFTM.MM" in e0 and "SFTM.EDGE" in e0
    # fused levels emit no edge work of their own
    assert not sde.e.get(1, []) and not sde.e.get(2, [])


def test_simulator_runs_kernel_schedule():
    from repro.core import isa, simulator

    g = graphs.random_graph(150, 600, seed=5, model="powerlaw")
    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    for name in ("gcn", "gat"):
        _, c = _compiled(name)
        r = simulator.simulate_model(isa.emit_sde(c.schedule(True)), ts)
        assert r.cycles > 0 and r.macs > 0


def test_edge_feature_weighted_gather_dispatches_and_runs():
    """recvSrc * w_e -> sendDstSum with a per-edge INPUT weight must select
    the weighted-SpMM block, and the runner must evaluate it (edge inputs
    are read lazily, never fed to apply_compute)."""
    from repro.core.trace import trace_model

    def build(tr, g):
        x = tr.input_vertex(8, "x")
        w = tr.input_edge(1, "w")
        tr.mark_output(g.gather_sum(g.scatter_src(x) * w))

    tr = trace_model(build, name="edge-weighted-sum")
    c = compiler.compile_gnn(tr)
    assert c.schedule(True).gather_kernel(0) == schedule.KERNEL_SPMM_WEIGHTED

    g = graphs.random_graph(100, 420, seed=8, model="powerlaw")
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    ref = executor.run_reference(tr, g, inputs, params)
    ts = tiling.grid_tile(g, 3, 3, sparse=True)
    out = pipeline.run_pipelined(c, g, ts, inputs, params,
                                 kernel_dispatch=True)
    assert float(jnp.max(jnp.abs(ref[0] - out[0]))) < TOL


def test_multigraph_parallel_edges_stay_exact():
    """Parallel edges keep their weight in the softmax (on the tile grid a
    tile's ``cnt`` equal scores add ``log cnt``) — GAT on a multigraph still
    matches the oracle."""
    import numpy as np

    src = np.array([0, 0, 0, 1, 2, 2, 3, 3], np.int32)  # two (0->4), two (3->5)
    dst = np.array([4, 4, 5, 4, 5, 6, 5, 5], np.int32)
    g = graphs.Graph(src=src, dst=dst, n_vertices=8, name="multi")
    tr, c = _compiled("gat", dim=8)
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    ref = executor.run_reference(tr, g, inputs, params)
    ts = tiling.grid_tile(g, 2, 2, sparse=True)
    out = pipeline.run_pipelined(c, g, ts, inputs, params,
                                 kernel_dispatch=True)
    assert float(jnp.max(jnp.abs(ref[0] - out[0]))) < 1e-4
