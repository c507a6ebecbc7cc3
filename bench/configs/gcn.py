"""Plain reference and work counts of the stacked GCN (ZIPPER §8.1).

Layer ``l``: ``x <- relu(sum_{j->i} dn_j dn_i (x_j W_l))`` with
``dn = 1/sqrt(max(in_degree, 1))``, the per-edge normalised form.  The
reference is plain ``jax.numpy`` in float32 and imports nothing of the
program: it is given the graph's (src, dst), the seeded inputs and weights,
and the dot to use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import aggregation_bytes, dims


def dnorm(dst, n_vertices):
    """``1 / sqrt(max(in_degree, 1))`` per vertex, as a column."""
    deg = np.bincount(np.asarray(dst), minlength=n_vertices)
    return (1.0 / np.sqrt(np.maximum(deg, 1.0))).astype(np.float32)[:, None]


def vertex_inputs(x, src, dst, n_vertices):
    """The program's inputs besides the features: the degree norm."""
    return {"x": x, "dnorm": dnorm(dst, n_vertices)}


def forward(params, x, src, dst, *, n_vertices, n_layers, dot):
    """The stacked layers' outputs, as a list (one output)."""
    deg = jax.ops.segment_sum(jnp.ones_like(dst, jnp.float32), dst,
                              num_segments=n_vertices)
    dn = jax.lax.rsqrt(jnp.maximum(deg, 1.0))
    w_e = (dn[src] * dn[dst])[:, None]
    for l in range(n_layers):
        h = dot(x, params[f"l{l}.W"])
        x = jax.nn.relu(jax.ops.segment_sum(h[src] * w_e, dst,
                                            num_segments=n_vertices))
    return [x]


def model_flops(cfg, n_vertices, n_edges):
    """FLOPs of one forward by the layer equations: the transform
    (2 V Fi Fo), the edge norm (E), the weighted messages and their sum
    (2 E Fo) and the relu (V Fo)."""
    V, E = n_vertices, n_edges
    return float(sum(2 * V * fi * fo + E + 2 * E * fo + V * fo
                     for fi, fo in dims(cfg)))


def kernel_work(cfg, n_vertices, n_edges):
    """(FLOPs, bytes) the weighted aggregation must do in one forward, at
    least: per layer 2 E Fo FLOPs; every source row read once and every
    output row written once (2 V Fo f32), one source index and one weight
    per edge, and V+1 row pointers."""
    V, E = n_vertices, n_edges
    flops = sum(2 * E * fo for _, fo in dims(cfg))
    byts = sum(aggregation_bytes(V, E, fo) for _, fo in dims(cfg))
    return float(flops), float(byts)
