"""Paper Fig 12 analogue: compiler (E2V) optimization speedup on GAT and
SAGE — naive per-edge implementations vs compiler-optimized, both on the
ZIPPER simulator and measured on the CPU pipelined executor (the paper also
reports the optimization's effect on its V100 baseline)."""
from __future__ import annotations

from repro.core import compiler, isa, pipeline, simulator, tiling
from repro.gnn import graphs, models

from .common import fmt_table, timeit, write_report


def run(quick: bool = False):
    g = graphs.paper_graph("cit-Patents", scale=0.002, seed=0)
    ts = tiling.grid_tile(g, 8, 8, sparse=True)
    rows = []
    for name in ("gat_naive", "sage_naive"):
        tr = models.trace_named(name)
        c_nv = compiler.compile_gnn(tr, optimize=False)
        c_opt = compiler.compile_gnn(tr, optimize=True)
        sim_nv = simulator.simulate_model(isa.emit_sde(c_nv.plan), ts)
        sim_opt = simulator.simulate_model(isa.emit_sde(c_opt.plan), ts)
        params = models.init_params(tr)
        inputs = models.init_inputs(tr, g)
        t_nv = timeit(pipeline.PipelinedRunner(c_nv, g, ts), inputs, params)
        t_opt = timeit(pipeline.PipelinedRunner(c_opt, g, ts), inputs, params)
        rows.append([name.replace("_naive", ""),
                     c_opt.opt_report["e2v_moved"],
                     f"{sim_nv.cycles/sim_opt.cycles:.2f}x",
                     f"{t_nv/t_opt:.2f}x"])
    headers = ["model", "ops_hoisted", "sim_speedup", "cpu_measured_speedup"]
    print("== Fig 12: E2V compiling optimization ==")
    print(fmt_table(rows, headers))
    write_report("bench_e2v", {"headers": headers, "rows": rows})
    return rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
