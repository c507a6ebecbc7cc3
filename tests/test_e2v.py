"""E2V compiler-optimization tests (paper §6.2, Fig 8b / Fig 12)."""
import jax.numpy as jnp
import pytest

from repro.core import compiler, isa, pipeline, tiling
from repro.gnn import graphs, models


def _edge_compute_ops(prog):
    return [n for s in prog.edge_segments() for n in s.nodes.values()
            if n.op in ("matmul", "gemv", "bias_add", "relu", "add", "mul")]


def test_e2v_moves_gat_mvs():
    """The two attention mat-vecs on edges move to the vertex segment."""
    tr = models.trace_named("gat_naive")
    c = compiler.compile_gnn(tr)
    assert c.opt_report["e2v_moved"] >= 2
    naive_gemvs = [n for s in c.naive_ir.edge_segments()
                   for n in s.nodes.values() if n.op == "gemv"]
    opt_gemvs = [n for s in c.ir.edge_segments()
                 for n in s.nodes.values() if n.op == "gemv"]
    assert len(naive_gemvs) == 2 and len(opt_gemvs) == 0


def test_e2v_moves_sage_pool_mlp():
    c = compiler.compile_gnn(models.trace_named("sage_naive"))
    assert c.opt_report["e2v_moved"] >= 3  # matmul + bias_add + relu chain
    assert not _edge_compute_ops(c.ir)


def test_e2v_does_not_move_bmm():
    """R-GCN's edge-type BMM depends on per-edge data: must NOT be hoisted."""
    c = compiler.compile_gnn(models.trace_named("rgcn"))
    assert c.opt_report["e2v_moved"] == 0
    assert any(n.op == "bmm_edge" for s in c.ir.edge_segments()
               for n in s.nodes.values())


@pytest.mark.parametrize("name", ["gat_naive", "sage_naive"])
def test_e2v_numerically_equivalent(name):
    g = graphs.random_graph(150, 600, seed=2)
    tr = models.trace_named(name, 16, 16)
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    ts = tiling.grid_tile(g, 3, 3)
    c_opt = compiler.compile_gnn(tr, optimize=True)
    c_naive = compiler.compile_gnn(tr, optimize=False)
    o1 = pipeline.run_pipelined(c_opt, g, ts, inputs, params,
                                kernel_dispatch=True)
    o2 = pipeline.run_pipelined(c_naive, g, ts, inputs, params,
                                kernel_dispatch=True)
    for a, b in zip(o1, o2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_e2v_reduces_simulated_cost():
    """The point of E2V: per-edge work becomes per-vertex work."""
    from repro.core import simulator
    g = graphs.paper_graph("ak2010", scale=0.05, seed=0)
    ts = tiling.grid_tile(g, 4, 4)
    tr = models.trace_named("gat_naive")
    sde_naive = isa.emit_sde(compiler.compile_gnn(tr, optimize=False).plan)
    sde_opt = isa.emit_sde(compiler.compile_gnn(tr, optimize=True).plan)
    r_naive = simulator.simulate_model(sde_naive, ts)
    r_opt = simulator.simulate_model(sde_opt, ts)
    assert r_opt.cycles < r_naive.cycles
