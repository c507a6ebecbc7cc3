"""Plain reference and work counts of the stacked R-GCN encoder
(Schlichtkrull et al., arXiv:1703.06103, eq. 2 with the block
decomposition of §2.2).

Layer ``l``: ``h_i <- sum_r sum_{j in N_i^r} (1 / c_{i,r}) W_r h_j + W_0 h_i``
with ``c_{i,r} = |N_i^r|``, each ``W_r`` block-diagonal (``l.W_rel``:
(R, B, k, m)), ReLU after every layer but the last.  Plain ``jax.numpy`` in
float32, in edge chunks so that no more than a chunk's block weights are
gathered at once, with each destination's sum taken in two parts
(:func:`destination_sum`); it imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import F32, dims

#: edges per chunk: 4096 edges' block weights (100 x 5 x 5 f32) are 41 MB
CHUNK = 4096


def destination_sum(msg, dst, n_segments):
    """Each destination's messages summed in two parts, the messages
    rounded to bf16 and the f32 remainder.  A plain f32 sum over a hub's
    thousands of messages depends on their order at ~2e-6 of the output's
    scale, as much as a program's whole error; the bf16 parts add with far
    less rounding, so the reference's own order matters ~4x less."""
    hi = jax.lax.reduce_precision(msg, exponent_bits=8, mantissa_bits=7)
    return (jax.ops.segment_sum(hi, dst, num_segments=n_segments)
            + jax.ops.segment_sum(msg - hi, dst, num_segments=n_segments))


def relation_norm(dst, etype, n_vertices):
    """``1 / c_{i,r}`` per edge: one over the edges of its relation into its
    destination."""
    key = np.asarray(etype, np.int64) * n_vertices + np.asarray(dst)
    _, inv, cnt = np.unique(key, return_inverse=True, return_counts=True)
    return (1.0 / cnt[inv.reshape(-1)]).astype(np.float32)


def edge_inputs(src, dst, etype, n_vertices):
    """The program's edge inputs: the relation id and the norm, columns."""
    return {"etype": np.asarray(etype, np.float32)[:, None],
            "rnorm": relation_norm(dst, etype, n_vertices)[:, None]}


def forward(params, x, src, dst, etype, rnorm, *, n_vertices, n_layers, dot):
    """The stacked layers' outputs, as a list (one output)."""
    E = src.shape[0]
    n_chunks = -(-E // CHUNK)
    pad = n_chunks * CHUNK - E

    def chunked(a, fill):
        return jnp.pad(a, (0, pad), constant_values=fill).reshape(n_chunks,
                                                                  CHUNK)

    cs, ce, cn = chunked(src, 0), chunked(etype, 0), chunked(rnorm, 0.0)
    cd = chunked(dst, n_vertices)       # padded edges land in a spare row
    for l in range(n_layers):
        w = params[f"l{l}.W_rel"]
        r, nb, k, m = w.shape

        def messages(c, x=x, w=w):
            s, e, nrm = c
            xs = x[s].reshape(CHUNK, nb, 1, k)
            return dot(xs, w[e]).reshape(CHUNK, nb * m) * nrm[:, None]

        msg = jax.lax.map(messages, (cs, ce, cn)).reshape(-1, nb * m)
        agg = destination_sum(msg, cd.reshape(-1), n_vertices + 1)[:n_vertices]
        h = agg + dot(x, params[f"l{l}.W_self"])
        x = jax.nn.relu(h) if l < n_layers - 1 else h
    return [x]


def _block(cfg):
    """Rows ``k`` of one relation block (in_dim / blocks)."""
    return cfg["in_dim"] // cfg["blocks"]


def model_flops(cfg, n_vertices, n_edges):
    """FLOPs of one forward by the layer equations: the self transform
    (2 V Fi Fo), per edge the block-diagonal product (2 Fo k) and the
    normed sum into the destination (2 Fo), the self term's add (V Fo) and
    the ReLU of every layer but the last (V Fo)."""
    V, E, k = n_vertices, n_edges, _block(cfg)
    n = cfg["layers"]
    return float(sum(2 * V * fi * fo + 2 * E * fo * k + 2 * E * fo
                     + V * fo + (V * fo if l < n - 1 else 0)
                     for l, (fi, fo) in enumerate(dims(cfg))))


def kernel_work(cfg, n_vertices, n_edges):
    """(FLOPs, bytes) the typed aggregation must do in one forward, at
    least: per layer and edge the block-diagonal product (2 Fo k) and the
    normed sum (2 Fo); every source row read once and every output row
    written once (2 V F f32), one source index, one relation and one norm
    per edge, and the relations' block weights (R F k f32)."""
    V, E, k, R = n_vertices, n_edges, _block(cfg), cfg["relations"]
    flops = sum(2 * E * fo * k + 2 * E * fo for _, fo in dims(cfg))
    byts = sum((2 * V * fo + 3 * E + R * fi * k) * F32
               for fi, fo in dims(cfg))
    return float(flops), float(byts)
