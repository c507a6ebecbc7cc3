"""Quickstart: the whole ZIPPER pipeline on one small graph, in ~40 lines.

    PYTHONPATH=src python examples/quickstart.py

Traces a 2-layer GCN written against the classic whole-graph programming
model (one trace spanning both layers), compiles it to the graph-native IR
(cross-layer CSE + E2V optimization included), tiles the graph (sparse
tiling + degree-sort reordering via the one-stop ``build_tiles`` entry),
checks the tiled runner against the whole-graph oracle under both its
schedules — the scan-only program and the Pallas-kernel program — and runs
the cycle-level simulator for the ZIPPER ASIC and a TPU-v5e-like config,
with the inter-layer pipelined schedule compared against the barrier
schedule.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax.numpy as jnp

from repro.core import compiler, executor, isa, pipeline, simulator, tiling
from repro.core.streams import HWConfig, TPU_V5E_LIKE
from repro.gnn import graphs, models


def main():
    g0 = graphs.paper_graph("ak2010", scale=0.1, seed=0)
    print(f"graph: {g0.n_vertices} vertices, {g0.n_edges} edges")

    # 1. trace a 2-layer GCN (one program), compile to graph-native IR
    tr = models.trace_stacked("gcn", 2)
    c = compiler.compile_gnn(tr)
    print(f"IR: {c.n_layers} layers, {len(c.ir.segments)} segments, "
          f"{c.plan.max_level + 1} phases, opt report {c.opt_report}")

    # 2. reorder + sparse-tile (one-stop entry, degree sorting opted in)
    tiles, r = tiling.build_tiles(g0, 8, 8, sparse=True, reorder="degree")
    print(f"tiles: {tiles.n_tiles} (S_max={tiles.s_max}, E_max={tiles.e_max}); "
          f"src loads {tiles.src_vertex_loads()} vs regular "
          f"{tiling.grid_tile(r.graph, 8, 8, sparse=False).src_vertex_loads()}")

    # 3. execute: the oracle on the original graph; the runner on the
    #    reordered tiles, taking and returning original vertex ids
    params = models.init_params(tr)
    inputs = models.init_inputs(tr, g0)
    ref = executor.run_reference(tr, g0, inputs, params)
    for kd, label in ((False, "scan"), (True, "kernel")):
        out = pipeline.run_pipelined(c, r.graph, tiles, inputs, params,
                                     kernel_dispatch=kd, reordering=r)
        print(f"max |oracle - runner ({label})| =",
              float(jnp.max(jnp.abs(ref[0] - out[0]))))

    # 4. simulate the hardware: barrier vs inter-layer pipelined schedule
    sde = isa.emit_sde(c.plan)
    for label, hw in [("ZIPPER (paper cfg)", HWConfig()), ("TPU-v5e-like", TPU_V5E_LIKE)]:
        s = simulator.simulate_model(sde, tiles, hw)
        p = simulator.simulate_model(sde, tiles, hw, inter_layer="pipelined")
        print(f"{label:18s}: {s.time_ms:7.2f} ms barrier, {p.time_ms:7.2f} ms "
              f"pipelined ({s.cycles / p.cycles:.2f}x), "
              f"MU util {s.utilization['MU']:.2f}, energy {s.energy_mj:.1f} mJ")


if __name__ == "__main__":
    main()
