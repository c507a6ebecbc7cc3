"""Static analysis & verification layer (ISSUE 6).

Pinned here: (a) all five paper models × {1,2,3} layers × both
kernel-dispatch settings analyze *clean* (zero error-severity diagnostics)
through the IR verifier, the schedule verifier, the exchange census, and the
task-graph race detector; (b) a negative-path suite — each seeded mutation
of a valid artifact is caught with its expected stable diagnostic code,
including a dropped drain dependency the hazard analyzer must flag as a
ZH201 race; (c) the ``compile_gnn(verify=True)`` default hook and the
satellite fixes (``rebuild_channels`` raising on orphaned recvs,
``toposort`` naming cycle members); (d) the static exchange census equals
``n_layers`` for every paper model (the HLO regex cross-check lives in
``test_sharded.py``).
"""
import copy
import random

import pytest

from repro.core import analysis as A
from repro.core import compiler, isa, tiling
from repro.core import ir as IR
from repro.core import schedule as S
from repro.core.streams import HWConfig, build_task_graph
from repro.gnn import graphs, models

DIM = 16


def _compiled(name, n_layers=2, dim=DIM, **kw):
    tr = models.trace_stacked(name, n_layers, dim, dim, dim)
    return compiler.compile_gnn(tr, **kw)


def _codes(diags):
    return {d.code for d in diags}


def _error_codes(diags):
    return {d.code for d in A.errors(diags)}


def _first(prog, pred):
    for seg in prog.segments:
        for n in seg.nodes.values():
            if pred(n):
                return seg, n
    raise AssertionError("no node matches")


# ---------------------------------------------------------------------------
# clean matrix: five paper models x {1,2,3} layers x both dispatch modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", models.PAPER_MODELS)
def test_paper_models_analyze_clean(name):
    for n_layers in (1, 2, 3):
        c = _compiled(name, n_layers)           # verify=True is the default
        diags = A.analyze(c)                    # IR + both schedules + census
        assert not A.errors(diags), (name, n_layers,
                                     A.format_report(diags, "dirty"))


@pytest.mark.parametrize("inter_layer", ["barrier", "pipelined"])
def test_task_graphs_analyze_clean(inter_layer):
    g = graphs.random_graph(150, 600, seed=3, model="powerlaw",
                            n_edge_types=3)
    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    for name in ("gcn", "gat"):
        c = _compiled(name, 2)
        sde = isa.emit_sde(c.schedule(True))
        tasks, _ = build_task_graph(sde, ts, HWConfig(),
                                    inter_layer=inter_layer)
        diags = A.analyze(tasks, sde=sde, tiles=ts, inter_layer=inter_layer)
        assert not A.errors(diags), A.format_report(diags, name)
        # structured Task identity: no label parsing needed downstream
        assert all(t.level >= 0 and t.part >= 0 and t.role for t in tasks)


def test_bucketed_and_per_chip_task_graphs_analyze_clean():
    g = graphs.random_graph(150, 600, seed=3, model="powerlaw")
    bt = tiling.bucket_tiles(tiling.grid_tile(g, 5, 5, sparse=True), 3)
    c = _compiled("gcn", 2)
    sde = isa.emit_sde(c.schedule(True))
    tasks, _ = build_task_graph(sde, bt, HWConfig(), inter_layer="pipelined",
                                parts=[0, 1])
    diags = A.analyze(tasks, sde=sde, tiles=bt, inter_layer="pipelined",
                      parts=[0, 1])
    assert not A.errors(diags), A.format_report(diags, "per-chip")
    # boundary reads landing on the other chip surface as info, not races
    assert "ZH206" in _codes(diags)


def test_static_exchange_census_counts_one_collective_per_layer():
    # the census invariant must hold for BOTH schedule variants — the
    # sharded runner executes either one, Pallas kernels on or off
    for name in models.PAPER_MODELS:
        for n_layers in (1, 2, 3):
            for dispatch in (False, True):
                sp = _compiled(name, n_layers).schedule(dispatch)
                cen = A.exchange_census(sp)
                assert cen.n_collectives == n_layers, \
                    (name, n_layers, dispatch, cen.events)
                assert cen.publish <= cen.tainted   # nothing untainted moves
                assert not A.verify_exchange(sp)


def test_sharded_runner_publish_set_matches_static_census():
    """The census is only a proof if it derives the SAME publish set the
    runner actually drains — check the dynamic set against the static one
    for scan and kernel schedules alike."""
    from repro.core.pipeline import ShardedRunner
    g = graphs.random_graph(120, 480, seed=7, model="powerlaw")
    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    for name in ("gcn", "gat", "ggnn"):        # spmm_w / segsoftmax / spmm
        c = _compiled(name, 2)
        for dispatch in (False, True):
            r = ShardedRunner(c, g, ts, 1, kernel_dispatch=dispatch)
            cen = A.exchange_census(c.schedule(dispatch))
            assert r._publish == set(cen.publish), (name, dispatch)


# ---------------------------------------------------------------------------
# negative paths: IR verifier (ZA0xx)
# ---------------------------------------------------------------------------

def test_orphaned_recv_is_caught_and_rebuild_channels_raises():
    c = _compiled("gcn")
    prog = copy.deepcopy(c.ir)
    _, recv = _first(prog, IR.IRNode.is_recv)
    recv.comm_id = 9999
    assert "ZA009" in _error_codes(A.verify_ir(prog))
    # satellite regression: rebuild_channels must raise, not drop, the recv
    with pytest.raises(ValueError, match="recv comm 9999 has no send"):
        prog.rebuild_channels()
    with pytest.raises(ValueError, match="has no send"):
        prog.validate()


def test_channel_dim_mismatch_is_caught():
    prog = copy.deepcopy(_compiled("gcn").ir)
    _, recv = _first(prog, IR.IRNode.is_recv)
    recv.dim += 3
    assert "ZA008" in _error_codes(A.verify_ir(prog))


def test_unknown_op_is_caught_and_op_unit_strict_raises():
    prog = copy.deepcopy(_compiled("gcn").ir)
    _, n = _first(prog, lambda n: n.op == "mul")
    n.op = "frobnicate"
    assert "ZA001" in _error_codes(A.verify_ir(prog))
    assert IR.op_unit("frobnicate") == "CTRL"         # legacy: silent bucket
    with pytest.raises(ValueError, match="not in the IR vocabulary"):
        IR.op_unit("frobnicate", strict=True)


def test_broadcast_and_contraction_dim_mutations_are_caught():
    prog = copy.deepcopy(_compiled("gat").ir)
    _, n = _first(prog, lambda n: n.op in IR.ELW_BINARY)
    n.dim += 5
    assert "ZA004" in _error_codes(A.verify_ir(prog))

    prog = copy.deepcopy(_compiled("gcn").ir)
    _, mm = _first(prog, lambda n: n.op == "matmul")
    mm.attrs["wshape"] = (mm.attrs["wshape"][0] + 1, mm.attrs["wshape"][1])
    assert "ZA005" in _error_codes(A.verify_ir(prog))


def test_cycle_is_caught_and_toposort_names_the_nodes():
    prog = copy.deepcopy(_compiled("gcn").ir)
    seg, n = _first(prog, lambda n: not n.is_recv() and n.inputs)
    dep = seg.nodes[n.inputs[0]]
    dep.inputs.append(n.id)
    assert "ZA003" in _error_codes(A.verify_ir(prog))
    # satellite regression: the exception names the cycle members
    with pytest.raises(ValueError,
                       match=rf"cycle in segment {seg.label}:.*%{n.id}"):
        seg.toposort()


def test_layer_monotonicity_violation_is_caught():
    prog = copy.deepcopy(_compiled("gcn").ir)
    _, n = _first(prog, lambda n: n.layer == 0 and n.inputs)
    seg, dep = _first(prog, lambda m: m.id == n.inputs[0])
    dep.layer = 1
    assert "ZA012" in _error_codes(A.verify_ir(prog))


def test_dead_node_and_unused_channel_warn_not_error():
    prog = copy.deepcopy(_compiled("gcn").ir)
    seg = prog.segments[0]
    _, src = _first(prog, lambda n: n.inputs)
    seg.add(IR.IRNode(id=prog.fresh_id(), op="relu", inputs=[src.inputs[0]],
                      dim=seg.nodes[src.inputs[0]].dim))
    diags = A.verify_ir(prog)
    assert not A.errors(diags)
    assert "ZA013" in _codes(diags)

    prog = copy.deepcopy(_compiled("gcn").ir)
    _, recv = _first(prog, lambda n: n.op == "recvSrc")
    for sg in prog.segments:
        for m in sg.nodes.values():
            m.inputs = [i for i in m.inputs if i != recv.id]
    diags = A.verify_ir(prog)
    assert "ZA014" in _codes(diags)
    assert "ZA014" not in _error_codes(diags)


def test_recv_with_inputs_is_caught():
    prog = copy.deepcopy(_compiled("gcn").ir)
    seg, recv = _first(prog, IR.IRNode.is_recv)
    other = next(n for n in seg.nodes.values() if n.id != recv.id)
    recv.inputs = [other.id]
    assert "ZA015" in _error_codes(A.verify_ir(prog))


# ---------------------------------------------------------------------------
# negative paths: schedule verifier (ZS1xx)
# ---------------------------------------------------------------------------

def _gather_blocks(sp):
    return [(ph, g) for ph in sp.phases for g in ph.gathers]


def test_swapped_kernel_tag_is_caught():
    sp = copy.deepcopy(_compiled("gcn").schedule(True))
    ph, g = next((ph, g) for ph, g in _gather_blocks(sp)
                 if g.kernel != S.KERNEL_SCAN)
    swapped = (S.KERNEL_SPMM if g.kernel != S.KERNEL_SPMM
               else S.KERNEL_SPMM_WEIGHTED)
    g.kernel = swapped
    want = {S.KERNEL_SPMM: "ZS104", S.KERNEL_SPMM_WEIGHTED: "ZS105"}[swapped]
    assert want in _error_codes(A.verify_schedule(sp))


def test_softmax_tag_on_non_softmax_gather_is_caught():
    sp = copy.deepcopy(_compiled("gcn").schedule(True))
    ph, g = next((ph, g) for ph, g in _gather_blocks(sp)
                 if g.kernel != S.KERNEL_SCAN)
    g.kernel = S.KERNEL_SEGMENT_SOFTMAX
    codes = _error_codes(A.verify_schedule(sp))
    assert "ZS106" in codes or "ZS103" in codes


def test_gather_ownership_and_covered_overlap_are_caught():
    sp = copy.deepcopy(_compiled("gcn").schedule(True))
    blocks = _gather_blocks(sp)
    assert len(blocks) >= 2
    (_, g0), (_, g1) = blocks[0], blocks[1]
    g0.covered.add(g1.acc.send_id)        # g1's channel now has two owners
    codes = _error_codes(A.verify_schedule(sp))
    assert "ZS101" in codes


def test_covered_node_leaking_into_a_block_is_caught():
    sp = copy.deepcopy(_compiled("gcn").schedule(True))
    ph, g = next((ph, g) for ph, g in _gather_blocks(sp)
                 if g.kernel != S.KERNEL_SCAN)
    leaked = sp.prog.find_node(g.acc.value_id)[1]
    ph.edge.nodes.append(leaked)
    assert "ZS109" in _error_codes(A.verify_schedule(sp))


def test_fused_levels_mutation_is_caught():
    sp = copy.deepcopy(_compiled("gat").schedule(True))
    ph, g = next((ph, g) for ph, g in _gather_blocks(sp)
                 if g.kernel == S.KERNEL_SEGMENT_SOFTMAX)
    g.fused_levels = (g.fused_levels[0], g.fused_levels[1],
                      g.fused_levels[2] + 7)
    codes = _error_codes(A.verify_schedule(sp))
    assert "ZS103" in codes or "ZS106" in codes


def test_dropped_output_store_is_caught():
    sp = copy.deepcopy(_compiled("gcn").schedule(True))
    for ph in reversed(sp.phases):
        if sp.outputs[0] in ph.dst.store_ids:
            ph.dst.store_ids.remove(sp.outputs[0])
            break
    else:
        raise AssertionError("output never stored")
    assert "ZS107" in _error_codes(A.verify_schedule(sp))


def test_accum_spec_mutation_is_caught():
    sp = copy.deepcopy(_compiled("gcn").schedule(True))
    _, g = _gather_blocks(sp)[0]
    g.acc.kind = "max" if g.acc.kind != "max" else "sum"
    assert "ZS111" in _error_codes(A.verify_schedule(sp))


def test_phase_layer_tag_regression_is_caught():
    sp = copy.deepcopy(_compiled("gcn").schedule(True))
    sp.phases[-1].layer = 0                  # layers must be monotone
    assert "ZS108" in _error_codes(A.verify_schedule(sp))


def test_missed_kernel_lint_explains_scan_fallbacks():
    # sage: max-reduce aggregate has no kernel; the lint says why
    sp = _compiled("sage").schedule(True)
    lints = [d for d in A.verify_schedule(sp) if d.code == "ZS110"]
    assert lints and all(d.severity == A.INFO for d in lints)
    assert any("max-reduce" in d.message for d in lints)
    # rgcn: the typed bmm gather runs on the relation kernel — no lint; a
    # schedule without the relation layout (typed=False) says why it scans
    sp = _compiled("rgcn").schedule(True)
    assert sp.gather_kernel(0) == S.KERNEL_RELATION
    assert not [d for d in A.verify_schedule(sp) if d.code == "ZS110"]
    sp = _compiled("rgcn").schedule(True, typed=False)
    lints = [d for d in A.verify_schedule(sp) if d.code == "ZS110"]
    assert lints and all("bmm_edge" in d.message for d in lints)
    # without kernel dispatch the scan path is intended: no lint
    sp = _compiled("sage").schedule(False)
    assert not [d for d in A.verify_schedule(sp) if d.code == "ZS110"]


# ---------------------------------------------------------------------------
# negative paths: hazard analyzer & census (ZH2xx)
# ---------------------------------------------------------------------------

def _pipelined_graph(name="gcn", n_layers=2):
    g = graphs.random_graph(150, 600, seed=3, model="powerlaw")
    ts = tiling.grid_tile(g, 4, 4, sparse=True)
    c = _compiled(name, n_layers)
    sde = isa.emit_sde(c.schedule(True))
    tasks, _ = build_task_graph(sde, ts, HWConfig(), inter_layer="pipelined")
    return tasks, sde, ts


def test_dropped_drain_dependency_is_flagged_as_race():
    """Acceptance: the race analyzer must flag a drain-ordering hazard."""
    tasks, sde, ts = _pipelined_graph()
    victim = next(
        t for t in tasks if t.role == "s" and any(
            tasks[d].role == "drain" and tasks[d].part != t.part
            for d in t.deps))
    dropped = next(d for d in victim.deps
                   if tasks[d].role == "drain" and tasks[d].part != victim.part)
    victim.deps.remove(dropped)
    diags = A.analyze_task_graph(tasks, sde=sde, tiles=ts,
                                 inter_layer="pipelined")
    races = [d for d in diags if d.code == "ZH201"]
    assert races and any(d.block == victim.label for d in races)
    assert any(f"partition {tasks[dropped].part}" in d.message for d in races)


def test_barrier_mode_ordering_violation_is_flagged():
    g = graphs.random_graph(100, 400, seed=5)
    ts = tiling.grid_tile(g, 3, 3, sparse=True)
    c = _compiled("gcn", 2)
    sde = isa.emit_sde(c.schedule(True))
    tasks, _ = build_task_graph(sde, ts, HWConfig(), inter_layer="barrier")
    # cut a mid-chain d-task loose: downstream levels lose the global barrier
    victim = next(t for t in tasks if t.kind == "d" and t.level == 1 and t.deps)
    victim.deps.clear()
    diags = A.analyze_task_graph(tasks, sde=sde, tiles=ts,
                                 inter_layer="barrier")
    assert "ZH201" in _error_codes(diags)


def test_corrupt_task_graph_structure_is_flagged():
    tasks, sde, ts = _pipelined_graph()
    tasks[0].deps.append(len(tasks) + 5)        # unknown/forward reference
    diags = A.analyze_task_graph(tasks, sde=sde, tiles=ts,
                                 inter_layer="pipelined")
    assert _error_codes(diags) == {"ZH202"}


def test_barrier_not_covering_its_tiles_is_flagged():
    tasks, sde, ts = _pipelined_graph()
    barrier = next(t for t in tasks if t.role == "barrier" and len(t.deps) > 1)
    barrier.deps.pop()
    diags = A.analyze_task_graph(tasks, sde=sde, tiles=ts,
                                 inter_layer="pipelined")
    assert "ZH203" in _error_codes(diags)


def test_census_mismatch_and_untainted_exchange_are_flagged():
    sp = copy.deepcopy(_compiled("gcn").schedule(False))
    sp.n_layers += 1
    assert "ZH204" in _error_codes(A.verify_exchange(sp))

    sp = copy.deepcopy(_compiled("gcn").schedule(False))
    _, h = _first(sp.prog, lambda n: n.op == "matmul")   # untainted h = xW
    sp.outputs.append(h.id)
    diags = A.verify_exchange(sp)
    assert any(d.code == "ZH205" and d.node == h.id for d in diags)


@pytest.mark.parametrize("seed", range(6))
def test_seeded_mutation_of_kernel_schedule_is_flagged(seed):
    """The negative paths must cover the KERNEL-dispatch variant too —
    seeded structural corruptions of the schedule the sharded Pallas path
    executes may not slip past the census + schedule verifier."""
    rng = random.Random(seed)
    name = rng.choice(["gcn", "gat", "ggnn"])
    sp = copy.deepcopy(_compiled(name, 2).schedule(True))
    kind = rng.choice(["layer_count", "untainted_publish", "dropped_phase"])
    if kind == "layer_count":
        sp.n_layers += rng.randint(1, 2)
        assert "ZH204" in _error_codes(A.verify_exchange(sp)), (name, kind)
    elif kind == "untainted_publish":
        _, h = _first(sp.prog, lambda n: n.op == "matmul")
        sp.outputs.append(h.id)
        diags = A.verify_exchange(sp)
        assert any(d.code == "ZH205" and d.node == h.id for d in diags), \
            (name, kind)
    else:
        # drop a gather-bearing phase: its collective disappears from the
        # replayed event stream, so the per-layer census count breaks
        victim = next(ph for ph in reversed(sp.phases) if ph.gathers)
        sp.phases.remove(victim)
        diags = A.verify_exchange(sp) + A.verify_schedule(sp)
        assert _error_codes(diags), (name, kind)


# ---------------------------------------------------------------------------
# compile-time hook, analyze() dispatch, diagnostics plumbing
# ---------------------------------------------------------------------------

def test_compile_gnn_verifies_by_default_and_collects_diagnostics():
    c = _compiled("sage")                     # verify=True is the default
    assert c.verify
    c.schedule(True)
    assert any(d.code == "ZS110" for d in c.diagnostics)
    assert not A.errors(c.diagnostics)
    # opt-out still compiles and keeps the hook off for later lowerings
    c2 = _compiled("sage", verify=False)
    c2.schedule(True)
    assert not c2.diagnostics


def test_verification_error_carries_diagnostics():
    d = A.Diagnostic("ZA008", "send dim 4 != recv dim 7", node=3)
    err = A.VerificationError([d], context="unit")
    assert err.diagnostics == [d]
    assert "ZA008" in str(err) and "unit" in str(err)
    with pytest.raises(ValueError, match="unknown diagnostic code"):
        A.Diagnostic("ZZ999", "nope")


def test_analyze_dispatches_on_artifact_type():
    c = _compiled("gcn")
    assert not A.errors(A.analyze(c.ir))
    assert not A.errors(A.analyze(c.schedule(True)))
    assert not A.errors(A.analyze(c))
    with pytest.raises(TypeError):
        A.analyze(42)


def test_diagnostic_formatting_and_code_registry():
    assert all(sev in A.SEVERITIES and meaning
               for sev, meaning in A.CODES.values())
    d = A.Diagnostic("ZS107", "value read early", phase=2, node=9,
                     block="dst")
    assert d.severity == A.ERROR
    assert "%9" in d.anchor and "phase 2" in d.anchor
    assert d.to_dict()["code"] == "ZS107"
    report = A.format_report([d], title="t")
    assert "ZS107" in report and "1 error" in report


def test_cli_runs_clean_and_fail_on_gates():
    from repro.analyze import main
    assert main(["--models", "gcn", "--layers", "1"]) == 0
    # sage emits ZS110 info findings: --fail-on info must gate on them
    assert main(["--models", "sage", "--layers", "1",
                 "--fail-on", "info"]) == 1
    assert main(["--models", "sage", "--layers", "1"]) == 0


# ---------------------------------------------------------------------------
# randomized sweep (hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # pragma: no cover
    pass                                              # deterministic sweep above still runs
else:
    @pytest.mark.slow
    @settings(max_examples=15, deadline=None)
    @given(name=st.sampled_from(list(models.PAPER_MODELS)),
           n_layers=st.integers(1, 3),
           dim=st.sampled_from([4, 8, 16]))
    def test_analysis_clean_property(name, n_layers, dim):
        c = _compiled(name, n_layers, dim=dim)
        diags = A.analyze(c)
        assert not A.errors(diags), A.format_report(diags, "dirty")
