"""Work counts shared by the models' files: the layers' widths and the
least bytes an aggregation must move.  They follow from V, E and the
configuration alone, never from tiled or padded shapes, so no change to
tiling, layout or kernels moves the yardstick."""
from __future__ import annotations

F32 = 4   # bytes


def dims(cfg):
    """(F_in, F_out) of each layer."""
    n = cfg["layers"]
    return [(cfg["in_dim"] if l == 0 else cfg["hidden_dim"],
             cfg["out_dim"] if l == n - 1 else cfg["hidden_dim"])
            for l in range(n)]


def aggregation_bytes(n_vertices, n_edges, f_out):
    """The least bytes one layer's aggregation moves: every source row read
    once and every output row written once (2 V Fo f32), one source index
    and one weight or score per edge, and V+1 row pointers."""
    V, E = n_vertices, n_edges
    return 2 * V * f_out * F32 + E * 2 * F32 + (V + 1) * F32
