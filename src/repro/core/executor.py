"""The whole-graph oracle and the op semantics every engine shares.

* :func:`run_reference` — whole-graph oracle (the classic programming model,
  "DGL-functional" semantics): every op over the full vertex/edge tensors.
  This is both the correctness oracle and the paper's non-tiled baseline.
* :func:`apply_compute`, :func:`typed_transform` and :func:`slot_edge_value`
  — one definition of each compute op, of R-GCN's block-diagonal typed
  transform and of a relation gather's per-edge weight, which the
  tiled engines in ``core/pipeline.py`` evaluate inside their scheduled
  blocks.  ``_NEG_INF`` (an empty max-reduce) and ``_HIGHEST`` (f32 dots)
  fix the numerics they all share.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

from ..gnn.graphs import Graph

Array = Any

_NEG_INF = -1e30  # used instead of -inf so max-reduce stays NaN-free on empty segments
#: f32 weights keep f32 accuracy on the TPU's MXU (its default is one bf16
#: pass); the reference and every engine share these dots
_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# shared op semantics
# ---------------------------------------------------------------------------

def apply_compute(op: str, attrs: Dict, params: Dict[str, Array], args: Sequence[Array]) -> Array:
    if op == "matmul" or op == "gemv":
        return jnp.matmul(args[0], params[attrs["weight"]], precision=_HIGHEST)
    if op == "bias_add":
        return args[0] + params[attrs["weight"]]
    if op == "bmm_edge":
        return typed_transform(args[0], args[1][..., 0].astype(jnp.int32),
                               params[attrs["weight"]])
    if op == "add":
        return args[0] + args[1]
    if op == "sub":
        return args[0] - args[1]
    if op == "mul":
        return args[0] * args[1]
    if op == "div":
        return args[0] / args[1]
    if op == "max2":
        return jnp.maximum(args[0], args[1])
    if op == "min2":
        return jnp.minimum(args[0], args[1])
    if op == "relu":
        return jax.nn.relu(args[0])
    if op == "leaky_relu":
        return jnp.where(args[0] > 0, args[0], attrs.get("slope", 0.2) * args[0])
    if op == "exp":
        return jnp.exp(args[0])
    if op == "sigmoid":
        return jax.nn.sigmoid(args[0])
    if op == "tanh":
        return jnp.tanh(args[0])
    if op == "neg":
        return -args[0]
    if op == "identity":
        return args[0]
    if op == "sqrt":
        return jnp.sqrt(args[0])
    if op == "rsqrt":
        return jax.lax.rsqrt(args[0])
    raise NotImplementedError(op)


#: per-chunk budget of gathered block weights (f32 elements, 64 MB):
#: bounds the typed transform's memory whatever the edge count
_TYPED_CHUNK_ELEMS = 1 << 24


def typed_transform(x: Array, et: Array, w: Array) -> Array:
    """``out_e = x_e @ blockdiag(w[et_e])`` for every row ``e``.

    ``w`` is (n_types, n_blocks, k, m).  Rows run in chunks whose gathered
    block weights (chunk, n_blocks, k, m) stay within ``_TYPED_CHUNK_ELEMS``,
    so no (E, d_in, d_out) weight is ever materialized; each block product
    is an f32 multiply-add over its k inputs (exact, no MXU pass)."""
    w = jnp.asarray(w)
    n_rows = x.shape[0]
    _, nb, k, m = w.shape
    chunk = max(1, min(n_rows, _TYPED_CHUNK_ELEMS // (nb * k * m)))
    n_chunks = -(-n_rows // chunk)
    pad = n_chunks * chunk - n_rows
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(n_chunks, chunk, nb, k)
    eb = jnp.pad(et, (0, pad)).reshape(n_chunks, chunk)

    def one(args):
        xc, ec = args
        return jnp.sum(xc[..., None] * w[ec], axis=-2).reshape(chunk, nb * m)

    out = jax.lax.map(one, (xb, eb))
    return out.reshape(n_chunks * chunk, nb * m)[:n_rows]


def slot_edge_value(g, sp, params, full, estore, rel,
                    n_vertices: int) -> Array:
    """A relation gather block's per-edge weight on the rows of a relation
    layout (``rel``: its ``slot_*`` arrays): the block's edge closure with
    edge inputs read by ``slot_gid`` and endpoint values (``full(nid)``: a
    vertex value on every row) by ``slot_src``/``slot_dst``; 1 where the
    block has no weight, 0 on padded rows."""
    valid = rel["slot_dst"] < n_vertices
    if g.weight_id is None:
        return valid.astype(jnp.float32)
    gid = rel["slot_gid"]
    env: Dict[int, Array] = {}

    def look(nid: int) -> Array:
        if nid in env:
            return env[nid]
        e = estore[nid]
        # a width-1 input is gathered as a flat vector, not (E, 1) rows
        return e[:, 0][gid][:, None] if e.shape[1] == 1 else e[gid]

    rows = {"recvSrc": rel["slot_src"],
            "recvDst": jnp.minimum(rel["slot_dst"], n_vertices - 1)}
    for n in g.edge_nodes:
        if n.op in rows:
            env[n.id] = full(sp.scatter_value_of[n.id])[rows[n.op]]
        else:
            env[n.id] = apply_compute(n.op, n.attrs, params,
                                      [look(i) for i in n.inputs])
    return jnp.where(valid, look(g.weight_id)[:, 0], 0.0)


# ---------------------------------------------------------------------------
# whole-graph reference (oracle / non-tiled baseline)
# ---------------------------------------------------------------------------

def run_reference(tr, graph: Graph, inputs: Dict[str, Array],
                  params: Dict[str, Array]) -> List[Array]:
    src = jnp.asarray(graph.src)
    dst = jnp.asarray(graph.dst)
    V = graph.n_vertices
    env: Dict[int, Array] = {}
    outs: List[Array] = []
    for n in tr.nodes:
        if n.op == "param":
            continue
        if n.op == "input":
            env[n.id] = jnp.asarray(inputs[n.attrs["name"]])
        elif n.op == "output":
            outs.append(env[n.inputs[0]])
        elif n.op == "scatter_src":
            env[n.id] = env[n.inputs[0]][src]
        elif n.op == "scatter_dst":
            env[n.id] = env[n.inputs[0]][dst]
        elif n.op == "gather":
            e = env[n.inputs[0]]
            red = n.attrs["reduce"]
            if red == "sum":
                env[n.id] = jax.ops.segment_sum(e, dst, num_segments=V)
            elif red == "max":
                m = jax.ops.segment_max(e, dst, num_segments=V)
                env[n.id] = jnp.maximum(m, _NEG_INF)  # empty segments -> -1e30 not -inf
            elif red == "mean":
                s = jax.ops.segment_sum(e, dst, num_segments=V)
                c = jax.ops.segment_sum(jnp.ones((e.shape[0], 1), e.dtype), dst, num_segments=V)
                env[n.id] = s / jnp.maximum(c, 1.0)
            else:
                raise ValueError(red)
        elif n.op in ("matmul", "gemv", "bias_add"):
            w = tr.node(n.inputs[1])
            env[n.id] = apply_compute(n.op, {"weight": w.attrs["name"]}, params, [env[n.inputs[0]]])
        elif n.op == "bmm_edge":
            w = tr.node(n.inputs[1])
            env[n.id] = apply_compute("bmm_edge", {"weight": w.attrs["name"]}, params,
                                      [env[n.inputs[0]], env[n.inputs[2]]])
        else:
            env[n.id] = apply_compute(n.op, n.attrs, params, [env[i] for i in n.inputs])
    return outs
