"""Multi-layer lowering study: per-layer vs fused-schedule simulated cycles.

ZIPPER's evaluation runs stacked GNNs (§8.1); this bench quantifies what the
cross-layer lowering buys on the cit-Patents-like configuration:

* ``per_layer_cycles`` — L independent single-layer programs, summed (the
  pre-multi-layer execution model: one host-level barrier per layer);
* ``fused_barrier_cycles`` — ONE compiled program spanning all layers,
  scheduled with full gather barriers between levels;
* ``fused_pipelined_cycles`` — the same program with layer boundaries
  relaxed to their true data dependencies (``inter_layer="pipelined"``):
  next-layer tile compute interleaves with the previous layer's gather
  drain, the paper's tile × operator parallelism applied across layers.

Also reported: the cross-layer CSE count (stacked GCN dedupes its re-emitted
normalized-adjacency scatters) and wall-clock of the fused multi-layer
``PipelinedRunner`` vs running a single-layer runner L times (one jit and
zero host round-trips vs L compiled calls).
"""
from __future__ import annotations

from repro.core import compiler, isa, pipeline, simulator, tiling
from repro.gnn import graphs, models

from .common import fmt_table, timeit, write_report


def run(quick: bool = False, smoke: bool = False, layers: int = 2):
    if smoke:
        g = graphs.paper_graph("cit-Patents", scale=0.001, seed=0,
                               n_edge_types=3)
        model_names = ("gcn", "gat")
        grid = 6
    else:
        g = graphs.paper_graph("cit-Patents", scale=0.002, seed=0,
                               n_edge_types=3)
        model_names = (models.PAPER_MODELS[:2] if quick
                       else models.PAPER_MODELS)
        grid = 8
    ts = tiling.grid_tile(g, grid, grid, sparse=True)

    rows = []
    metrics = {}
    for name in model_names:
        single = compiler.compile_gnn(models.trace_named(name))
        stacked = compiler.compile_gnn(models.trace_stacked(name, layers))
        sde_single = isa.emit_sde(single.schedule(False))
        sde_stacked = isa.emit_sde(stacked.schedule(False))
        per_layer = simulator.simulate_model(sde_single, ts).cycles * layers
        barrier = simulator.simulate_model(sde_stacked, ts).cycles
        pipelined = simulator.simulate_model(sde_stacked, ts,
                                             inter_layer="pipelined").cycles
        rows.append([name, layers,
                     stacked.opt_report["cse_removed"],
                     per_layer, barrier, pipelined,
                     f"{barrier / pipelined:.3f}x"])
        metrics[name] = dict(layers=layers,
                             cse_removed=stacked.opt_report["cse_removed"],
                             per_layer_cycles=per_layer,
                             fused_barrier_cycles=barrier,
                             fused_pipelined_cycles=pipelined)
    headers = ["model", "layers", "cse_removed", "per_layer_cycles",
               "fused_barrier_cycles", "fused_pipelined_cycles",
               "pipeline_speedup"]
    print(f"== multi-layer lowering: barrier vs pipelined ({layers} layers, "
          "cit-Patents-like) ==")
    print(fmt_table(rows, headers))

    # wall-clock: L single-layer runner calls (host round-trip per layer)
    # vs one fused multi-layer jit.  GGNN keeps both variants on the same
    # pure-SpMM kernel path, so the comparison isolates the schedule; on CPU
    # expect rough parity (the structural win is the simulated overlap
    # above — XLA-CPU cannot interleave the layer boundary itself).
    wall_rows = []
    if not smoke and "ggnn" in model_names:
        dim = 32
        tr1 = models.trace_named("ggnn", dim, dim)
        trL = models.trace_stacked("ggnn", layers, dim, dim, dim)
        c1, cL = compiler.compile_gnn(tr1), compiler.compile_gnn(trL)
        r1 = pipeline.PipelinedRunner(c1, g, ts)
        rL = pipeline.PipelinedRunner(cL, g, ts)
        p1 = models.init_params(tr1)
        pL = models.init_params(trL)
        inputs = models.init_inputs(trL, g)

        def chained():
            x = inputs["x"]
            for _ in range(layers):
                x = r1({"x": x}, p1)[0]
            return x

        repeats = 1 if quick else 3
        t_chain = timeit(chained, repeats=repeats)
        t_fused = timeit(lambda: rL(inputs, pL), repeats=repeats)
        wall_rows = [[f"{layers}x single-layer runner", f"{t_chain*1e3:.1f}ms"],
                     ["fused multi-layer runner", f"{t_fused*1e3:.1f}ms"]]
        print("\n== wall-clock: chained per-layer vs fused (ggnn) ==")
        print(fmt_table(wall_rows, ["executor", "median_wall"]))
        metrics["ggnn"]["wall_chained_s"] = t_chain
        metrics["ggnn"]["wall_fused_s"] = t_fused

    write_report("bench_multilayer",
                 {"headers": headers, "rows": rows, "metrics": metrics,
                  "wall": wall_rows})
    return metrics


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer models")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph, two models (CI smoke)")
    ap.add_argument("--layers", type=int, default=2,
                    help="stack depth for the fused schedules")
    args = ap.parse_args()
    run(quick=args.quick, smoke=args.smoke, layers=args.layers)
