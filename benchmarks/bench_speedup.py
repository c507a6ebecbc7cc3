"""Paper Fig 9/10 analogue: end-to-end speedup + energy.

Columns per (model × dataset):
  * cpu_whole_graph_s — measured: the classic whole-graph execution (the
    paper's DGL-CPU baseline role), jit-compiled JAX on this host;
  * cpu_pipelined_s  — measured: ZIPPER tiling + scan-pipelined execution
    on the same host (software benefit of the tiling alone);
  * zipper_sim_ms    — simulated: ZIPPER ASIC (paper Table-4 config);
  * zipper_energy_mJ — simulated energy (paper §8.1 model);
  * tpu_sim_ms       — simulated: TPU-v5e-like config (hardware adaptation).

Graphs are the paper's datasets at reduced scale (structure preserved);
the simulated speedups are scale-free comparisons against the same-sized
baseline, so trends are comparable with the paper's Fig 9/10.
"""
from __future__ import annotations

import functools

import jax

from repro.core import compiler, executor, isa, pipeline, reorder, simulator, tiling
from repro.core.streams import HWConfig, TPU_V5E_LIKE
from repro.gnn import graphs, models

from .common import BENCH_GRAPHS, fmt_table, timeit, write_report


def run(quick: bool = False, layers: int = 1):
    rows = []
    # two datasets in the default run (per-model jit compiles dominate);
    # the tiling/E2V/stream benches cover the remaining datasets' trends
    bench_graphs = dict(list(BENCH_GRAPHS.items())[:1 if quick else 2])
    model_names = models.PAPER_MODELS[:2] if quick else models.PAPER_MODELS
    for ds, scale in bench_graphs.items():
        g0 = graphs.paper_graph(ds, scale=scale, seed=0, n_edge_types=3)
        r = reorder.degree_sort(g0)
        ts = tiling.grid_tile(r.graph, 8, 8, sparse=True)
        for name in model_names:
            tr = (models.trace_named(name) if layers == 1
                  else models.trace_stacked(name, layers))
            c = compiler.compile_gnn(tr)
            params = models.init_params(tr)
            inputs0 = models.init_inputs(tr, g0)
            inputs = {k: (r.permute_vertex_features(v) if v.shape[0] == g0.n_vertices
                          else v) for k, v in inputs0.items()}

            whole = jax.jit(lambda i, p: executor.run_reference(tr, r.graph, i, p))
            t_whole = timeit(whole, inputs, params)
            runner = pipeline.PipelinedRunner(c, r.graph, ts)
            t_pipe = timeit(runner, inputs, params)

            sde = isa.emit_sde(c.plan)
            sim = simulator.simulate_model(sde, ts, HWConfig())
            sim_tpu = simulator.simulate_model(sde, ts, TPU_V5E_LIKE)
            rows.append([ds, name,
                         f"{t_whole*1e3:.1f}", f"{t_pipe*1e3:.1f}",
                         f"{t_whole/t_pipe:.2f}x",
                         f"{sim.time_ms:.2f}", f"{t_whole*1e3/sim.time_ms:.0f}x",
                         f"{sim.energy_mj:.2f}",
                         f"{sim_tpu.time_ms:.2f}"])
    headers = ["dataset", "model", "cpu_whole_ms", "cpu_tiled_ms", "sw_speedup",
               "zipper_sim_ms", "sim_speedup_vs_cpu", "zipper_energy_mJ",
               "tpuv5e_sim_ms"]
    print(f"== Fig 9/10: speedup & energy (layers={layers}) ==")
    print(fmt_table(rows, headers))
    write_report("bench_speedup",
                 {"headers": headers, "rows": rows, "layers": layers})
    return rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sweep")
    ap.add_argument("--layers", type=int, default=1,
                    help="stack depth of the benchmarked models")
    args = ap.parse_args()
    run(quick=args.quick, layers=args.layers)
