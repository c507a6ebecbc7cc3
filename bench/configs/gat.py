"""Plain reference and work counts of the stacked single-head GAT
(ZIPPER §8.1).

Layer ``l``: ``h = x W_l``; edge score ``e_ji = leaky_relu(h_j a_src +
h_i a_dst, 0.2)``; ``alpha = softmax of e over the edges into i``;
``x_i <- sum_j alpha_ji h_j`` (no activation between layers, as the
program's layer equations have it).  Plain ``jax.numpy`` in float32; it
imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.work import aggregation_bytes, dims


def vertex_inputs(x, src, dst, n_vertices):
    """The program's vertex inputs: the features alone."""
    return {"x": x}


def forward(params, x, src, dst, *, n_vertices, n_layers, dot):
    """The stacked layers' outputs, as a list (one output)."""
    for l in range(n_layers):
        h = dot(x, params[f"l{l}.W"])
        es = dot(h, params[f"l{l}.a_src"])[:, 0]
        ed = dot(h, params[f"l{l}.a_dst"])[:, 0]
        e = es[src] + ed[dst]
        e = jnp.where(e > 0, e, 0.2 * e)
        m = jax.ops.segment_max(e, dst, num_segments=n_vertices)
        p = jnp.exp(e - m[dst])
        s = jax.ops.segment_sum(p, dst, num_segments=n_vertices)
        alpha = (p / s[dst])[:, None]
        x = jax.ops.segment_sum(h[src] * alpha, dst,
                                num_segments=n_vertices)
    return [x]


def model_flops(cfg, n_vertices, n_edges):
    """FLOPs of one forward by the layer equations: the transform
    (2 V Fi Fo), the two score mat-vecs (4 V Fo), per edge the score sum,
    leaky relu, max, shift, exp, sum and divide (7 E), and the weighted
    messages and their sum (2 E Fo)."""
    V, E = n_vertices, n_edges
    return float(sum(2 * V * fi * fo + 4 * V * fo + 7 * E + 2 * E * fo
                     for fi, fo in dims(cfg)))


def kernel_work(cfg, n_vertices, n_edges):
    """(FLOPs, bytes) the segment-softmax aggregation must do in one
    forward, at least: per layer, per edge the running max, shift, exp and
    sum (4 E) and the weighted sum (2 E Fo), and one divide per output
    (V Fo); every source row read once and every output row written once
    (2 V Fo f32), one source index and one score per edge, and V+1 row
    pointers."""
    V, E = n_vertices, n_edges
    flops = sum(4 * E + 2 * E * fo + V * fo for _, fo in dims(cfg))
    byts = sum(aggregation_bytes(V, E, fo) for _, fo in dims(cfg))
    return float(flops), float(byts)
