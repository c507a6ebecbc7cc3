"""Paper Fig 13 analogue: multi-stream / hybrid-architecture design-space
exploration — latency vs (#s/eStreams, #MU, #VU), normalized to the paper's
reference point (2 streams, 1 MU, 2 VU).

``--smoke`` (or ``run(smoke=True)``) exercises the full pipeline on a tiny
graph with a minimal sweep — importable from tier-1 tests as a fast
end-to-end check of the compile → schedule → ISA → simulate path.
"""
from __future__ import annotations

from repro.core import compiler, isa, simulator, tiling
from repro.core.streams import HWConfig
from repro.gnn import graphs, models

from .common import fmt_table, write_report


def run(quick: bool = False, smoke: bool = False, layers: int = 1):
    if smoke:
        g = graphs.random_graph(200, 800, seed=0, model="powerlaw",
                                n_edge_types=3)
        ts = tiling.grid_tile(g, 4, 4, sparse=True)
        model_names = ("gcn", "gat")
        sweep = [(2,), (1,), (2,)]
    else:
        g = graphs.paper_graph("cit-Patents", scale=0.002, seed=0, n_edge_types=3)
        ts = tiling.grid_tile(g, 8, 8, sparse=True)
        model_names = (("gat", "sage") if quick
                       else ("gcn", "gat", "sage", "ggnn", "rgcn"))
        sweep = [(2, 4, 8), (1, 2), (2, 4)]
    streams_sw, mu_sw, vu_sw = sweep

    rows = []
    for name in model_names:
        tr = (models.trace_named(name) if layers == 1
              else models.trace_stacked(name, layers))
        sde = isa.emit_sde(compiler.compile_gnn(tr).plan)
        base = simulator.simulate_model(
            sde, ts, HWConfig(n_sstreams=2, n_estreams=2, n_mu=1, n_vu=2)).cycles
        for streams in streams_sw:
            for n_mu in mu_sw:
                for n_vu in vu_sw:
                    r = simulator.simulate_model(
                        sde, ts, HWConfig(n_sstreams=streams, n_estreams=streams,
                                          n_mu=n_mu, n_vu=n_vu))
                    rows.append([name, streams, n_mu, n_vu,
                                 f"{base/r.cycles:.2f}x",
                                 f"{r.utilization['MU']:.2f}",
                                 f"{r.utilization['VU']:.2f}"])
    headers = ["model", "s/e_streams", "MU", "VU", "speedup_vs_(2,1,2)",
               "MU_util", "VU_util"]
    print("== Fig 13: stream/unit design-space exploration ==")
    print(fmt_table(rows, headers))
    # smoke runs report under their own name so the CI artifact keeps the
    # full-sweep history distinct from the per-PR smoke trajectory
    write_report("bench_streams_smoke" if smoke else "bench_streams",
                 {"headers": headers, "rows": rows})
    return rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny graph + minimal sweep (CI smoke)")
    ap.add_argument("--layers", type=int, default=1,
                    help="stack depth of the benchmarked models")
    args = ap.parse_args()
    run(quick=args.quick, smoke=args.smoke, layers=args.layers)
