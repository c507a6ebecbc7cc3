"""GNN execution engines.

* :func:`run_reference` — whole-graph oracle (the classic programming model,
  "DGL-functional" semantics): every op over the full vertex/edge tensors.
  This is both the correctness oracle and the paper's non-tiled baseline.
* :func:`run_tiled` — faithful ZIPPER execution: an interpreter over the
  compiled :class:`~repro.core.schedule.ScheduledProgram`.  Source blocks run
  per tile on the (sparse-)compacted source rows, edge blocks run per tile,
  gather blocks accumulate into per-partition destination rows, destination
  blocks run per partition.  Gather blocks tagged with a Pallas kernel
  (``pallas_spmm`` / ``pallas_spmm_weighted`` / ``pallas_segment_softmax``)
  dispatch one batched kernel call over the tile set instead of the per-tile
  scan — the paper's run-time mapping of schedule steps onto hardware blocks.

The engine derives no levels or roles of its own: block membership comes
entirely from ``schedule.lower`` (single source of truth).  The jit/scan-
pipelined variant lives in ``core/pipeline.py``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import compiler as C
from . import schedule as S
from .tiling import TileSet
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


# ---------------------------------------------------------------------------
# tiled ZIPPER execution: ScheduledProgram interpreter
# ---------------------------------------------------------------------------

class _TiledRun:
    def __init__(self, compiled: C.CompiledGNN, graph: Graph, tiles: TileSet,
                 inputs: Dict[str, Array], params: Dict[str, Array],
                 kernel_dispatch: bool = True):
        self.sp: S.ScheduledProgram = compiled.schedule(kernel_dispatch)
        self.graph = graph
        self.tiles = tiles
        self.params = params
        # global (V, dim) store: inputs, gather results, dst-computed values
        self.vstore: Dict[int, Array] = {
            nid: jnp.asarray(inputs[name]) for nid, name in self.sp.vertex_inputs}
        # global (E, dim) store for edge inputs
        self.estore: Dict[int, Array] = {
            nid: jnp.asarray(inputs[name]) for nid, name in self.sp.edge_inputs}
        self._dense = None      # cached (adj, flags) for pure-SpMM blocks
        self._flags = None      # FIRST/LAST markers (runtime-densified blocks)

    # -- vertex-block evaluation ---------------------------------------------
    def _eval_vertex(self, nodes, rows: Array, store_ids=()) -> Dict[int, Array]:
        """Evaluate a Src/Dst block's node list on the given vertex rows.

        ``store_ids`` writes those results back into the global vstore
        (destination replica).  Returns the local env.
        """
        env: Dict[int, Array] = {}

        def lookup(nid: int) -> Array:
            if nid in env:
                return env[nid]
            if nid in self.vstore:
                return self.vstore[nid][rows]
            raise KeyError(f"vertex value %{nid} unavailable")

        for n in nodes:
            if n.op == "output":
                env[n.id] = lookup(n.inputs[0])
            else:
                env[n.id] = apply_compute(n.op, n.attrs, self.params,
                                          [lookup(i) for i in n.inputs])
            if n.id in store_ids:
                if n.id not in self.vstore:
                    self.vstore[n.id] = jnp.zeros(
                        (self.graph.n_vertices, env[n.id].shape[-1]), env[n.id].dtype)
                self.vstore[n.id] = self.vstore[n.id].at[rows].set(env[n.id])
        return env

    # -- edge-block evaluation (one tile) ------------------------------------
    def _eval_edge(self, nodes, senv: Dict[int, Array], src_rows: Array,
                   esrc: Array, edst_global: Array, egid: Array):
        """Evaluate an edge-block node list for one tile.

        Returns ``(eenv, elookup)``: the local env plus a lookup that falls
        back to the global edge-feature store for edge inputs.
        """
        eenv: Dict[int, Array] = {}

        def elookup(nid: int) -> Array:
            if nid in eenv:
                return eenv[nid]
            if nid in self.estore:
                return self.estore[nid][egid]
            raise KeyError(f"edge value %{nid} unavailable")

        for n in nodes:
            if n.op == "recvSrc":
                src_nid = self.sp.scatter_value_of[n.id]
                base = senv[src_nid] if src_nid in senv else self.vstore[src_nid][src_rows]
                eenv[n.id] = base[esrc]
            elif n.op == "recvDst":
                src_nid = self.sp.scatter_value_of[n.id]
                eenv[n.id] = self.vstore[src_nid][edst_global]
            else:
                eenv[n.id] = apply_compute(n.op, n.attrs, self.params,
                                           [elookup(i) for i in n.inputs])
        return eenv, elookup

    def _tile_coords(self, ti: int):
        t = self.tiles
        p = int(t.part_id[ti])
        src_rows = jnp.asarray(t.src_ids[ti])            # full padded row
        esrc = jnp.asarray(t.edge_src[ti])
        edst_global = jnp.minimum(
            jnp.asarray(t.edge_dst[ti]) + int(t.part_start[p]),
            self.graph.n_vertices - 1)
        egid = jnp.asarray(t.edge_gid[ti])
        return p, src_rows, esrc, edst_global, egid

    # -- kernel-tagged gather blocks -----------------------------------------
    def _run_relation(self, g: S.GatherBlock, phase: S.Phase) -> None:
        """A typed gather over the tile set's relation-grouped layout."""
        from ..kernels.relation.ops import layout_operands, relation_aggregate
        from .tiling import relation_layout

        V = self.graph.n_vertices
        bmm = next(seg.nodes[g.bmm_id] for seg in self.sp.prog.segments
                   if g.bmm_id in seg.nodes)
        rel = layout_operands(relation_layout(self.tiles,
                                              bmm.attrs["wshape"][0]))

        def full(nid: int) -> Array:
            if nid in self.vstore:
                return self.vstore[nid]
            return self._eval_vertex(phase.src.nodes, jnp.arange(V))[nid]

        scale = slot_edge_value(g, self.sp, self.params, full, self.estore,
                                rel, V)
        self.vstore[g.acc.recv_id] = relation_aggregate(
            full(g.src_value_id), scale, self.params[bmm.attrs["weight"]],
            rel, n_out=V)

    def _run_kernel_gathers(self, phase: S.Phase) -> None:
        from ..kernels.tile_spmm import ops as tops
        from ..kernels.tile_spmm.kernel import tile_flags

        t = self.tiles
        P = t.n_dst_parts
        dmax = int(t.part_size.max())
        if self._flags is None:
            self._flags = jnp.asarray(tile_flags(t.part_id))
        pmask = np.isin(np.arange(P), t.part_id)

        for g in phase.kernel_gathers():
            if g.kernel == S.KERNEL_RELATION:
                self._run_relation(g, phase)
                continue
            # per-tile source values (padded rows; padding never contributes)
            xsrc_rows = []
            edge_vals = []
            for ti in range(t.n_tiles):
                p, src_rows, esrc, edst_global, egid = self._tile_coords(ti)
                senv = self._eval_vertex(phase.src.nodes, src_rows)
                h = (senv[g.src_value_id] if g.src_value_id in senv
                     else self.vstore[g.src_value_id][src_rows])
                if g.kernel == S.KERNEL_SPMM:
                    xsrc_rows.append(h)
                    continue
                _, elookup = self._eval_edge(g.edge_nodes, senv, src_rows, esrc,
                                             edst_global, egid)
                if g.kernel == S.KERNEL_SPMM_WEIGHTED:
                    xsrc_rows.append(h)
                    edge_vals.append(elookup(g.weight_id)[:, 0])   # (E,)
                else:   # segment softmax: scores + per-edge source values
                    xsrc_rows.append(h[esrc])                      # (E, F)
                    edge_vals.append(elookup(g.score_id)[:, 0])    # (E,)
            xsrc = jnp.stack(xsrc_rows)
            part_id = jnp.asarray(t.part_id)
            n_edge = jnp.asarray(t.n_edge)

            if t.layout == "csr":
                # CSR tiles skip the densify pass entirely: the kernels walk
                # the per-tile row pointers over per-edge operands
                row_ptr = jnp.asarray(t.row_ptr)
                col = jnp.asarray(t.edge_src)
                if g.kernel == S.KERNEL_SEGMENT_SOFTMAX:
                    out = tops.gat_aggregate_csr(
                        row_ptr, jnp.stack(edge_vals), xsrc, part_id,
                        self._flags, n_parts=P)
                else:
                    if g.kernel == S.KERNEL_SPMM:
                        w = jnp.ones(col.shape, jnp.float32)
                    else:
                        w = jnp.stack(edge_vals)
                        emask = (jnp.arange(w.shape[1])[None, :]
                                 < n_edge[:, None])
                        w = jnp.where(emask, w, 0.0)
                    out = tops.spmm_csr(row_ptr, col, w, xsrc, part_id,
                                        self._flags, n_parts=P)
            elif g.kernel == S.KERNEL_SPMM:
                if self._dense is None:
                    self._dense = tops.densify_tiles(t)
                adj, flags = self._dense
                out = tops.spmm(jnp.asarray(adj), xsrc, part_id,
                                jnp.asarray(flags), n_parts=P)
            elif g.kernel == S.KERNEL_SPMM_WEIGHTED:
                adj = tops.densify_edge_weights(
                    jnp.stack(edge_vals), jnp.asarray(t.edge_dst),
                    jnp.asarray(t.edge_src), n_edge, dmax=dmax, smax=t.s_max)
                out = tops.spmm(adj, xsrc, part_id, self._flags, n_parts=P)
            else:
                scores = tops.densify_edge_scores(
                    jnp.stack(edge_vals), jnp.asarray(t.edge_dst), n_edge,
                    dmax=dmax)
                out = tops.gat_aggregate(scores, xsrc, part_id, self._flags,
                                         n_parts=P)
            # partitions with no tile are never written by the kernel
            out = jnp.where(jnp.asarray(pmask)[:, None, None], out, 0.0)
            buf = jnp.zeros((self.graph.n_vertices, out.shape[-1]), jnp.float32)
            for p in range(P):
                lo, n = int(t.part_start[p]), int(t.part_size[p])
                buf = buf.at[lo:lo + n].set(out[p, :n])
            self.vstore[g.acc.recv_id] = buf

    # -- main loop -----------------------------------------------------------
    def run(self) -> List[Array]:
        t = self.tiles
        V = self.graph.n_vertices
        for phase in self.sp.phases:
            # 1. destination/partition-scope block
            if phase.dst.store_ids:
                for p in range(t.n_dst_parts):
                    lo = int(t.part_start[p]); n = int(t.part_size[p])
                    if n == 0:
                        continue
                    rows = jnp.arange(lo, lo + n)
                    self._eval_vertex(phase.dst.nodes, rows,
                                      store_ids=set(phase.dst.store_ids))
            if not phase.has_tile_work:
                continue

            # 2. kernel-dispatched gather blocks (one batched call each)
            if phase.kernel_gathers():
                self._run_kernel_gathers(phase)

            scan_gathers = phase.scan_gathers()
            if not scan_gathers and not phase.edge.nodes:
                continue

            # 3. accumulators for the scan-path gathers
            acc_sum: Dict[int, Array] = {}
            acc_max: Dict[int, Array] = {}
            acc_cnt: Dict[int, Array] = {}
            for g in scan_gathers:
                cid, dim = g.acc.comm_id, g.acc.dim
                if g.acc.kind in ("sum", "mean"):
                    acc_sum[cid] = jnp.zeros((V, dim), jnp.float32)
                    if g.acc.kind == "mean":
                        acc_cnt[cid] = jnp.zeros((V, 1), jnp.float32)
                else:
                    acc_max[cid] = jnp.full((V, dim), _NEG_INF, jnp.float32)

            # 4. tile loop
            for ti in range(t.n_tiles):
                ns, ne = int(t.n_src[ti]), int(t.n_edge[ti])
                if ne == 0:
                    continue
                p = int(t.part_id[ti])
                src_rows = jnp.asarray(t.src_ids[ti, :ns])
                esrc = jnp.asarray(t.edge_src[ti, :ne])
                edst_global = jnp.asarray(t.edge_dst[ti, :ne]) + int(t.part_start[p])
                egid = jnp.asarray(t.edge_gid[ti, :ne])

                senv = self._eval_vertex(phase.src.nodes, src_rows)
                _, elookup = self._eval_edge(phase.edge.nodes, senv, src_rows,
                                             esrc, edst_global, egid)
                for g in scan_gathers:
                    cid = g.acc.comm_id
                    val = elookup(g.acc.value_id)
                    if g.acc.kind in ("sum", "mean"):
                        acc_sum[cid] = acc_sum[cid].at[edst_global].add(val)
                        if g.acc.kind == "mean":
                            acc_cnt[cid] = acc_cnt[cid].at[edst_global].add(
                                jnp.ones((val.shape[0], 1), jnp.float32))
                    else:
                        acc_max[cid] = acc_max[cid].at[edst_global].max(val)

            # 5. publish scan-gather results for the next phase
            for g in scan_gathers:
                cid = g.acc.comm_id
                if g.acc.kind == "sum":
                    self.vstore[g.acc.recv_id] = acc_sum[cid]
                elif g.acc.kind == "mean":
                    self.vstore[g.acc.recv_id] = acc_sum[cid] / jnp.maximum(
                        acc_cnt[cid], 1.0)
                else:
                    self.vstore[g.acc.recv_id] = acc_max[cid]

        return [self.vstore[o] for o in self.sp.outputs]


def run_tiled(compiled: C.CompiledGNN, graph: Graph, tiles: TileSet,
              inputs: Dict[str, Array], params: Dict[str, Array],
              kernel_dispatch: bool = True) -> List[Array]:
    """Interpret the compiled scheduled program tile-by-tile.

    ``kernel_dispatch=False`` forces every gather block onto the scan path
    (the paper's pure multi-phase schedule, no Pallas blocks).
    """
    return _TiledRun(compiled, graph, tiles, inputs, params,
                     kernel_dispatch=kernel_dispatch).run()
