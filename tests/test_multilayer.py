"""Multi-layer GNN programs (ISSUE 4): one lowering from stacked layers to a
cross-layer ScheduledProgram.

Pinned here: (a) ``trace_model`` accepts stacked layer builders and tags
every node with its layer; (b) all five paper models run stacked through
PipelinedRunner, which matches a whole-graph *layer-by-layer* oracle, and
through emit_sde + the simulator; (c) the cross-layer
CSE pass removes repeated structure-only ops on stacked GCN and E2V hoists
across layer boundaries; (d) the pipelined inter-layer schedule simulates
fewer cycles than the barrier schedule; (e) program signatures distinguish
layer counts.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiler, executor, isa, pipeline, simulator, tiling
from repro.core.streams import HWConfig, build_task_graph
from repro.gnn import graphs, models

DIM = 16
REL_TOL = 1e-4   # acceptance: engines match the layer-by-layer oracle


def _stacked(name, n_layers, dim=DIM):
    tr = models.trace_stacked(name, n_layers, dim, dim, dim)
    return tr, compiler.compile_gnn(tr)


def _layer_by_layer_oracle(name, n_layers, g, inputs, params, dim=DIM):
    """Chain n_layers SINGLE-layer whole-graph references: layer l's output
    becomes layer l+1's input, per-layer params stripped of their prefix.
    A model whose stack is linear only in its last layer (rgcn) gets the
    ReLU the stack applies between layers."""
    x = np.asarray(inputs["x"])
    for layer in range(n_layers):
        tr_l = models.trace_named(name, dim, dim)
        prefix = f"l{layer}."
        p_l = {k[len(prefix):]: v for k, v in params.items()
               if k.startswith(prefix)}
        inp_l = {"x": x}
        for shared in ("dnorm", "etype", "rnorm"):
            if shared in inputs:
                inp_l[shared] = inputs[shared]
        x = np.asarray(executor.run_reference(tr_l, g, inp_l, p_l)[0])
        if models.MODELS[name].last_linear and layer < n_layers - 1:
            x = np.maximum(x, 0.0)
    return x


def _rel_err(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / max(1.0, np.max(np.abs(a))))


# ---------------------------------------------------------------------------
# trace-level: stacked layer builders, layer tags
# ---------------------------------------------------------------------------

def test_trace_model_accepts_layer_builder_list():
    tr = models.trace_stacked("gcn", 3, 8, 8, 8)
    assert tr.n_layers == 3
    layers = set(tr.layer_of.values())
    assert layers == {0, 1, 2}
    # per-layer params, shared structure inputs declared once
    assert {"l0.W", "l1.W", "l2.W"} <= set(tr.params)
    input_names = [n.attrs["name"] for n in tr.nodes if n.op == "input"]
    assert input_names == ["x", "dnorm"]


def test_stacking_guards():
    """Misuse fails loudly: empty builder lists, GGNN dim changes, and
    n_layers conflicting with a pre-compiled model all raise."""
    from repro.core.trace import trace_model
    from repro.serve import InferenceServer

    with pytest.raises(ValueError, match="empty layer-builder"):
        trace_model([], name="m")
    with pytest.raises(ValueError, match="preserves the feature dim"):
        models.trace_stacked("ggnn", 2, 64, 128, 32)
    c = compiler.compile_gnn(models.trace_named("gcn", 8, 8))
    with pytest.raises(ValueError, match="conflicts"):
        InferenceServer(c, n_layers=2)
    # a builders list is reusable across traces (shared inputs reset)
    builders = models.build_stacked("gcn", 2, 8, 8, 8)
    assert trace_model(builders, "a").n_layers == 2
    assert trace_model(builders, "b").n_layers == 2


def test_single_layer_traces_unchanged_by_refactor():
    """The layer-fn refactor must not perturb single-layer traces (program
    signatures are cache keys in serving)."""
    for name in models.PAPER_MODELS:
        tr = models.trace_named(name, DIM, DIM)
        assert tr.n_layers == 1
        assert set(tr.layer_of.values()) == {0}


def test_scheduled_phases_carry_layer_tags():
    _, c = _stacked("gcn", 2)
    sp = c.schedule(False)
    assert sp.n_layers == 2
    assert [(p.level, p.layer) for p in sp.phases] == [(0, 0), (1, 1), (2, 1)]
    _, cg = _stacked("gat", 2)
    spg = cg.schedule(False)
    # GAT: 3 softmax levels per layer; the boundary sits at level 3
    assert spg.layer_of_level()[0] == 0 and spg.layer_of_level()[3] == 1
    sde = isa.emit_sde(spg)
    assert sde.n_layers == 2 and sde.layer_of(3) == 1


# ---------------------------------------------------------------------------
# acceptance: five paper models, stacked, two engines, one oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", models.PAPER_MODELS)
@pytest.mark.parametrize("n_layers", [2, 3])
def test_stacked_models_match_layer_by_layer_oracle(name, n_layers):
    g = graphs.random_graph(150, 600, seed=3, model="powerlaw", n_edge_types=3)
    tr, c = _stacked(name, n_layers)
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    oracle = _layer_by_layer_oracle(name, n_layers, g, inputs, params)

    # whole-graph reference on the stacked trace agrees with the chained
    # single-layer references (the stacked builders are the same layers)
    ref = executor.run_reference(tr, g, inputs, params)
    assert _rel_err(oracle, ref[0]) < REL_TOL

    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    bt = tiling.bucket_tiles(ts, 3)
    for kd in (False, True):
        for tiles, kind in ((ts, "plain"), (bt, "bucketed")):
            out = pipeline.run_pipelined(c, g, tiles, inputs, params,
                                         kernel_dispatch=kd)
            assert _rel_err(oracle, out[0]) < REL_TOL, (name, kind, kd)

    # second engine: the multi-layer program lowers to SDE instructions and
    # executes through the cycle simulator in ONE pass (both schedules)
    for kd in (False, True):
        r = simulator.simulate_model(isa.emit_sde(c.schedule(kd)), ts)
        assert r.cycles > 0 and r.macs > 0


# ---------------------------------------------------------------------------
# cross-layer optimization passes
# ---------------------------------------------------------------------------

def test_cross_layer_cse_removes_ops_on_stacked_gcn():
    """Acceptance: stacked GCN re-emits the structure-only normalized
    adjacency (scatter_src(dn) * scatter_dst(dn)) per layer; CSE must
    deduplicate it across layers."""
    _, c1 = _stacked("gcn", 1)
    _, c2 = _stacked("gcn", 2)
    _, c3 = _stacked("gcn", 3)
    assert c1.opt_report["cse_removed"] == 0
    assert c2.opt_report["cse_removed"] >= 1
    # one deduplicated motif per extra layer
    assert c3.opt_report["cse_removed"] > c2.opt_report["cse_removed"]
    # the optimized IR is genuinely smaller than the naive lowering
    assert c2.ir.op_count() < c2.naive_ir.op_count()


def test_cse_preserves_kernel_dispatch_on_stacked_gcn():
    """After dedup, every GCN layer's gather still pattern-matches onto a
    Pallas block (weighted SpMM: the shared edge-norm scalar is its α)."""
    from repro.core import schedule
    _, c = _stacked("gcn", 2)
    kernels = c.schedule(True).kernels_by_level()
    assert all(ks == [schedule.KERNEL_SPMM_WEIGHTED]
               for ks in kernels.values())
    assert len(kernels) == 2


def test_e2v_hoists_across_layer_boundaries():
    """A stacked naive-SAGE (per-edge pooling MLP in every layer) must get
    every layer's MLP hoisted by the global E2V pass."""
    from repro.core.trace import trace_model

    def make(layer):
        def build(tr, g, x):
            if x is None:
                x = tr.input_vertex(DIM, "x")
            return models.layer_sage(tr, g, x, DIM, prefix=f"l{layer}.",
                                     naive=True)
        return build

    tr = trace_model([make(0), make(1)], name="sage_naive_x2")
    c = compiler.compile_gnn(tr)
    # matmul+bias+relu hoisted per layer (>= 6 moves), none left on edges
    assert c.opt_report["e2v_moved"] >= 6
    for seg in c.ir.edge_segments():
        assert all(n.op not in ("matmul", "bias_add", "relu")
                   for n in seg.nodes.values())
    # and the hoisted program still matches the naive one numerically
    g = graphs.random_graph(100, 400, seed=5, model="powerlaw")
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g)
    ref = executor.run_reference(tr, g, inputs, params)
    ts = tiling.grid_tile(g, 3, 3, sparse=True)
    out = pipeline.run_pipelined(c, g, ts, inputs, params,
                                 kernel_dispatch=True)
    assert _rel_err(ref[0], out[0]) < REL_TOL


# ---------------------------------------------------------------------------
# inter-layer pipelining (streams / simulator)
# ---------------------------------------------------------------------------

def test_pipelined_task_graph_is_valid_and_faster():
    """Acceptance: the pipelined 2-layer schedule beats the barrier schedule
    on the cit-Patents-like configuration."""
    g = graphs.paper_graph("cit-Patents", scale=0.001, seed=0, n_edge_types=3)
    ts = tiling.grid_tile(g, 6, 6, sparse=True)
    for name in ("gcn", "gat"):
        _, c = _stacked(name, 2)
        sde = isa.emit_sde(c.schedule(False))
        tasks, _ = build_task_graph(sde, ts, HWConfig(),
                                    inter_layer="pipelined")
        for t in tasks:   # acyclic by construction order
            assert all(d < t.tid for d in t.deps)
        bar = simulator.simulate_model(sde, ts)
        pipe = simulator.simulate_model(sde, ts, inter_layer="pipelined")
        assert pipe.cycles < bar.cycles, (name, pipe.cycles, bar.cycles)
        # identical work, different schedule: op counts must not move
        assert (pipe.macs, pipe.elw_ops) == (bar.macs, bar.elw_ops)


def test_single_layer_unaffected_by_pipelined_mode():
    """Without a layer boundary the two modes build the identical DAG."""
    g = graphs.random_graph(120, 500, seed=1, model="powerlaw")
    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    c = compiler.compile_gnn(models.trace_named("gcn", DIM, DIM))
    sde = isa.emit_sde(c.schedule(False))
    bar = simulator.simulate_model(sde, ts)
    pipe = simulator.simulate_model(sde, ts, inter_layer="pipelined")
    assert bar.cycles == pipe.cycles


# ---------------------------------------------------------------------------
# serving-facing identity
# ---------------------------------------------------------------------------

def test_structure_signature_distinguishes_layer_counts():
    _, c1 = _stacked("gcn", 1)
    _, c2 = _stacked("gcn", 2)
    assert c1.structure_signature() != c2.structure_signature()
    assert c1.n_layers == 1 and c2.n_layers == 2
    sig2 = c2.schedule(True).structure_signature()
    assert c2.schedule(True).n_layers == 2 and sig2 == \
        c2.schedule(True).structure_signature()  # memoized & stable
