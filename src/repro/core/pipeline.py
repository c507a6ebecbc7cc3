"""Inter-tile pipelined execution in JAX (paper Fig 4c, adapted).

On the ZIPPER ASIC, tile pipelining comes from multiple hardware streams.
On TPU/XLA there is one instruction stream per core, but the same effect —
tile *t+1*'s data movement overlapped with tile *t*'s compute — falls out of
(a) ``lax.scan`` over the padded tile batch, which XLA software-pipelines,
and (b) the fused Pallas tile kernels (``kernels/tile_spmm`` +
``kernels/segment_softmax``), whose grid pipelining double-buffers the
HBM→VMEM DMA against the MXU.

This module is the scan-based engine: one jit-compiled function per
(compiled model × tile-set shape).  Like ``executor.run_tiled`` it is an
*interpreter* of the :class:`~repro.core.schedule.ScheduledProgram` — it
derives no levels or roles of its own.  Per phase:

* the destination block runs vectorized over partitions,
* gather blocks tagged ``pallas_spmm`` / ``pallas_spmm_weighted`` dispatch
  one densified kernel call per size bucket (partition outputs summed into
  the shared accumulators),
* a gather block tagged ``pallas_segment_softmax`` dispatches the online-
  softmax kernel over the unbucketed tile batch (softmax state cannot be
  merged across buckets) — GAT's three softmax phases in ONE kernel pass,
* ``scan``-tagged gathers run the pipelined ``lax.scan`` tile loop, one scan
  per bucket with shared accumulators.

``tiles`` may be a :class:`~repro.core.tiling.TileSet` (one global-pad
bucket) or a :class:`~repro.core.tiling.BucketedTileSet`.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import compiler as C
from . import schedule as S
from .executor import apply_compute, _NEG_INF
from .tiling import (BucketedTileSet, ShardPlan, TileSet, exchange_sets,
                     plan_shards)
from ..gnn.graphs import Graph

Array = Any


def _padded_partition_ids(tiles) -> Tuple[np.ndarray, int]:
    """(P, Dmax) global vertex ids per partition row; invalid slots -> V."""
    P = tiles.n_dst_parts
    dmax = int(tiles.part_size.max())
    V = tiles.n_vertices
    ids = np.full((P, dmax), V, dtype=np.int32)
    for p in range(P):
        n = int(tiles.part_size[p])
        ids[p, :n] = tiles.part_start[p] + np.arange(n, dtype=np.int32)
    return ids, dmax


def _tile_arrays(ts: TileSet) -> Dict[str, Array]:
    d = dict(
        src_ids=jnp.asarray(ts.src_ids), edge_src=jnp.asarray(ts.edge_src),
        edge_dst=jnp.asarray(ts.edge_dst), edge_gid=jnp.asarray(ts.edge_gid),
        n_src=jnp.asarray(ts.n_src), n_edge=jnp.asarray(ts.n_edge),
        part_id=jnp.asarray(ts.part_id), part_start=jnp.asarray(ts.part_start),
    )
    if ts.row_ptr is not None:
        d["row_ptr"] = jnp.asarray(ts.row_ptr)
    return d


def _perm_operand(reordering) -> Optional[Dict[str, Array]]:
    """Traced (order, rank) operand pair; ``None`` for the identity (the
    pytree structure is pinned by the runner's reorder-mode signature)."""
    if reordering is None or reordering.is_identity:
        return None
    return dict(order=jnp.asarray(reordering.order),
                rank=jnp.asarray(reordering.rank))


def _check_reorder_mode(expected: str, reordering) -> None:
    mode = "identity" if reordering is None else reordering.mode
    if mode != expected:
        raise ValueError(
            f"reordering mode {mode!r} does not match this runner's "
            f"compiled mode {expected!r}")


# ---- scan-gather accumulator semantics (shared by Pipelined/Sharded) -------
# The masking, mean-count, and _NEG_INF-clamp rules below are the single
# source of truth for the scan path; the two runners differ only in the
# accumulator's partition-row count (global P vs device-local P_loc) and in
# which per-tile id indexes it.

def _init_gather_acc(scan_gathers, n_rows: int, dmax: int) -> Dict[str, Array]:
    acc: Dict[str, Array] = {}
    for g in scan_gathers:
        cid, dim = g.acc.comm_id, g.acc.dim
        if g.acc.kind in ("sum", "mean"):
            acc[f"sum{cid}"] = jnp.zeros((n_rows, dmax, dim), jnp.float32)
            if g.acc.kind == "mean":
                acc[f"cnt{cid}"] = jnp.zeros((n_rows, dmax, 1), jnp.float32)
        else:
            acc[f"max{cid}"] = jnp.full((n_rows, dmax, dim), _NEG_INF,
                                        jnp.float32)
    return acc


def _gather_accumulate(acc: Dict[str, Array], g, val: Array, emask: Array,
                       edst: Array, pid: Array, dmax: int) -> None:
    """Fold one tile's per-edge values into the gather accumulator row
    ``pid`` (in place on the dict)."""
    cid = g.acc.comm_id
    if g.acc.kind in ("sum", "mean"):
        contrib = jax.ops.segment_sum(
            jnp.where(emask, val, 0.0), edst, num_segments=dmax)
        acc[f"sum{cid}"] = acc[f"sum{cid}"].at[pid].add(contrib)
        if g.acc.kind == "mean":
            cnt = jax.ops.segment_sum(
                jnp.where(emask, 1.0, 0.0), edst, num_segments=dmax)
            acc[f"cnt{cid}"] = acc[f"cnt{cid}"].at[pid].add(cnt[:, None])
    else:
        m = jax.ops.segment_max(
            jnp.where(emask, val, _NEG_INF), edst, num_segments=dmax)
        acc[f"max{cid}"] = acc[f"max{cid}"].at[pid].max(
            jnp.maximum(m, _NEG_INF))


def _drain_gather_acc(acc: Dict[str, Array], g) -> Array:
    cid = g.acc.comm_id
    if g.acc.kind == "sum":
        return acc[f"sum{cid}"]
    if g.acc.kind == "mean":
        return acc[f"sum{cid}"] / jnp.maximum(acc[f"cnt{cid}"], 1.0)
    return acc[f"max{cid}"]


class PipelinedRunner:
    """Builds and jits the scan/kernel-pipelined executor for one model.

    ``kernel_dispatch`` selects the scheduled program variant: ``True``
    routes pattern-matched gather blocks through the Pallas kernels,
    ``False`` (the default when no ``tile_kernel`` is given) interprets the
    pure multi-phase scan schedule.  ``tile_kernel`` overrides the SpMM
    kernel entry point (signature
    ``kernel(adj, xsrc, part_id, flags, *, n_parts) -> (P, Dmax, F)``).

    A runner's compilation depends only on its *structure signature* — the
    scheduled program plus the tile-set shapes (``signature`` property) —
    never on the concrete edge lists: every graph-specific array is a traced
    argument of the jitted function.  :meth:`bind` re-derives those operands
    for a different tile set with the same signature and :meth:`run_with`
    executes them through the already-compiled program, which is what the
    serving-layer program cache amortizes across requests.

    ``donate_inputs=True`` donates the request's input buffers to XLA on the
    hot path (the serving engine enables this off-CPU, where its padded
    per-request arrays are dead after the call).
    """

    def __init__(self, compiled: C.CompiledGNN, graph: Graph, tiles,
                 tile_kernel: Optional[Callable] = None,
                 kernel_dispatch: Optional[bool] = None,
                 donate_inputs: bool = False,
                 reordering=None):
        from ..kernels.tile_spmm import ops as tops

        if kernel_dispatch is None:
            kernel_dispatch = tile_kernel is not None
        self.c = compiled
        self.sp: S.ScheduledProgram = compiled.schedule(kernel_dispatch)
        self.graph = graph
        self.tiles = tiles
        self.layout = getattr(tiles, "layout", "coo")
        self.tile_kernel = tile_kernel if tile_kernel is not None else tops.spmm
        self.csr_kernel = tops.spmm_csr
        self.softmax_kernel = tops.gat_aggregate
        self.softmax_csr_kernel = tops.gat_aggregate_csr
        # ``graph`` (and the tiles) live in reordered vertex space when a
        # non-identity ``reordering`` is given; the runner permutes request
        # inputs in and outputs back, so callers stay in original ids
        self.reordering = reordering
        self.reorder_mode = ("identity" if reordering is None
                             else reordering.mode)
        self.part_ids_pad, self.dmax = _padded_partition_ids(tiles)
        self._kernels = {g.kernel for ph in self.sp.phases for g in ph.gathers}
        self._signature = (self.sp.structure_signature(),
                           tiles.shape_signature(), self.reorder_mode)
        self._operands: Optional[Tuple] = None   # lazy bind of ctor tiles
        self.donate_inputs = donate_inputs
        self._jitted = jax.jit(self._run,
                               donate_argnums=(0,) if donate_inputs else ())

    @property
    def signature(self) -> Tuple:
        """(program, tile-set) structural identity this compilation serves."""
        return self._signature

    def jit_cache_size(self) -> int:
        """Number of distinct XLA compilations behind this runner (expect 1
        after warmup; the serving tests assert no silent retraces)."""
        try:
            return int(self._jitted._cache_size())
        except AttributeError:   # older jax: no introspection, report unknown
            return -1

    # ------------------------------------------------------------- constants
    def _tile_const(self, ts: TileSet) -> Dict[str, Array]:
        """FIRST/LAST flags + partition presence mask for one tile batch."""
        from ..kernels.tile_spmm.kernel import tile_flags
        P = self.tiles.n_dst_parts
        return dict(flags=jnp.asarray(tile_flags(ts.part_id)),
                    pmask=jnp.asarray(np.isin(np.arange(P), ts.part_id)
                                      .astype(np.float32)))

    def _bucket_const(self, b: TileSet, with_adj: bool) -> Dict[str, Array]:
        """Per-bucket kernel metadata; dense adjacency only for pure SpMM
        over COO tiles (CSR kernels walk row pointers instead)."""
        from ..kernels.tile_spmm.ops import densify_tiles
        kc = self._tile_const(b)
        if with_adj and b.layout != "csr":
            adj, _ = densify_tiles(b)
            kc["adj"] = jnp.asarray(adj)
        return kc

    # ------------------------------------------------------------------ bind
    def bind(self, tiles, reordering=None) -> Tuple:
        """Device operands (tile arrays + kernel constants + permutation) for
        a tile set structurally identical to the construction one — the
        per-request rebind step the serving cache runs instead of
        re-jitting.  ``reordering`` must realize the same mode the runner
        was compiled with (its (order, rank) arrays are traced operands)."""
        if tiles.shape_signature() != self.tiles.shape_signature():
            raise ValueError(
                "tile set is not structurally identical to this runner's: "
                f"{tiles.shape_signature()} != {self.tiles.shape_signature()}")
        _check_reorder_mode(self.reorder_mode, reordering)
        buckets: List[TileSet] = (
            list(tiles.buckets) if isinstance(tiles, BucketedTileSet) else [tiles])
        tas = tuple(_tile_arrays(b) for b in buckets)
        if self._kernels & set(S.PALLAS_KERNELS):
            kcs = tuple(self._bucket_const(b, S.KERNEL_SPMM in self._kernels)
                        for b in buckets)
        else:
            kcs = tuple({} for _ in buckets)
        # the online-softmax state cannot be merged across buckets, so the
        # segment-softmax block always runs over the unbucketed tile batch
        ta0 = kc0 = None
        if S.KERNEL_SEGMENT_SOFTMAX in self._kernels:
            st = tiles.source if isinstance(tiles, BucketedTileSet) else tiles
            ta0 = _tile_arrays(st)
            kc0 = self._tile_const(st)
        return (tas, kcs, ta0, kc0, _perm_operand(reordering))

    # ------------------------------------------------------------------ run
    def _args(self, inputs, params, operands) -> Tuple:
        if operands is None:
            if self._operands is None:
                self._operands = self.bind(self.tiles, self.reordering)
            operands = self._operands
        return ({k: jnp.asarray(v) for k, v in inputs.items()},
                {k: jnp.asarray(v) for k, v in params.items()}) + operands

    def __call__(self, inputs: Dict[str, Array], params: Dict[str, Array],
                 operands: Optional[Tuple] = None) -> List[Array]:
        return self._jitted(*self._args(inputs, params, operands))

    def lower(self, inputs: Dict[str, Array], params: Dict[str, Array]):
        """``jax.stages.Lowered`` of this runner's program on the
        construction tiles (HLO inspection: which gather blocks became
        Mosaic kernels)."""
        return self._jitted.lower(*self._args(inputs, params, None))

    def run_with(self, tiles, inputs: Dict[str, Array],
                 params: Dict[str, Array], reordering=None) -> List[Array]:
        """Execute a different same-signature tile set through the warm
        compilation (no retrace: operand shapes are identical by contract)."""
        return self(inputs, params, operands=self.bind(tiles, reordering))

    # ---------------------------------------------------------- trace-time
    def _run(self, inputs, params, tas, kcs, ta0, kc0, perm) -> List[Array]:
        from ..kernels.tile_spmm.ops import (densify_edge_scores,
                                             densify_edge_weights)

        sp = self.sp
        V = self.graph.n_vertices
        P, dmax = self.tiles.n_dst_parts, self.dmax
        pad_ids = jnp.asarray(self.part_ids_pad)          # (P, Dmax), V = invalid
        pad_valid = (pad_ids < V)[..., None]              # (P, Dmax, 1)
        safe_pad_ids = jnp.minimum(pad_ids, V - 1)

        if perm is not None:
            # requests arrive in original vertex order; the tiles (and edge
            # arrays, which degree_sort leaves in place) live in reordered
            # space — permute vertex features in, outputs back at the end
            inputs = dict(inputs)
            for name in {name for _, name in sp.vertex_inputs}:
                inputs[name] = inputs[name][perm["order"]]

        vstore: Dict[int, Array] = {nid: inputs[name]
                                    for nid, name in sp.vertex_inputs}
        estore: Dict[int, Array] = {nid: inputs[name]
                                    for nid, name in sp.edge_inputs}

        # ---- gather-drain fusion across phase/layer boundaries -------------
        # A gather result lands in padded (P, Dmax, F) partition layout.  The
        # next phase's dst block reads it in exactly that layout, so keeping
        # it in ``pstore`` skips the unpad-scatter + re-gather round trip (the
        # "full barrier" between a layer's gather drain and the next layer's
        # destination compute).  Only values the tile-side paths read — src
        # recompute, edge recvSrc/recvDst, kernel X operands, outputs — are
        # published to the flat (V, F) vertex store.
        tile_side_reads = set(sp.outputs)
        tile_side_reads.update(sp.scatter_value_of.values())
        for ph in sp.phases:
            for n in ph.src.nodes:
                tile_side_reads.update(n.inputs)
            for gb in ph.gathers:
                if gb.src_value_id is not None:
                    tile_side_reads.add(gb.src_value_id)
        pstore: Dict[int, Array] = {}

        def publish_gather(recv_id, padded_val):
            pstore[recv_id] = padded_val
            if recv_id in tile_side_reads:
                vstore[recv_id] = unpad(padded_val)

        def eval_vertex(rows, nodes, padded=False):
            """rows: indices (per-tile (S,) / batched (T,S) / padded (P,Dmax));
            ``padded=True`` (dst blocks) short-circuits gather results still
            sitting in partition layout."""
            env: Dict[int, Array] = {}

            def lookup(nid):
                if nid in env:
                    return env[nid]
                if padded and nid in pstore:
                    return pstore[nid]
                return vstore[nid][rows]

            for n in nodes:
                if n.id not in env and n.id in vstore:
                    # value already drained by an earlier dst block (layer
                    # boundary): the source replica reads the stored rows
                    # instead of recomputing the previous layer per tile
                    continue
                if n.op == "output":
                    env[n.id] = lookup(n.inputs[0])
                else:
                    env[n.id] = apply_compute(n.op, n.attrs, params,
                                              [lookup(i) for i in n.inputs])
            return env

        def edge_env(nodes, xs, senv):
            """Edge-block evaluation for one tile slice ``xs``."""
            eenv: Dict[int, Array] = {}

            def elookup(nid):
                return eenv[nid] if nid in eenv else estore[nid][xs["edge_gid"]]

            for n in nodes:
                if n.op == "recvSrc":
                    src_nid = sp.scatter_value_of[n.id]
                    base = (senv[src_nid] if src_nid in senv
                            else vstore[src_nid][xs["src_ids"]])
                    eenv[n.id] = base[xs["edge_src"]]
                elif n.op == "recvDst":
                    src_nid = sp.scatter_value_of[n.id]
                    eenv[n.id] = vstore[src_nid][xs["dst_global"]]
                else:
                    eenv[n.id] = apply_compute(n.op, n.attrs, params,
                                               [elookup(i) for i in n.inputs])
            return eenv, elookup

        def with_dst(ta):
            """Per-tile scan/vmap operands: (T, ...) arrays only, with the
            global destination rows precomputed from the partition table."""
            xs = {k: ta[k] for k in ("src_ids", "edge_src", "edge_dst",
                                     "edge_gid", "n_edge", "part_id")}
            xs["dst_global"] = jnp.minimum(
                ta["part_start"][ta["part_id"]][:, None] + ta["edge_dst"], V - 1)
            return xs

        def src_value(senv, nid, rows):
            return senv[nid] if nid in senv else vstore[nid][rows]

        def unpad(val):
            """(P, Dmax, d) partition-padded -> (V, d) vertex store."""
            flat = jnp.where(pad_valid, val, 0.0).reshape(P * dmax, -1)
            buf = jnp.zeros((V + 1, flat.shape[-1]), jnp.float32)
            buf = buf.at[pad_ids.reshape(-1)].set(flat)  # invalid rows -> sentinel V
            return buf[:V]

        for phase in sp.phases:
            # ---- destination block (vectorized over partitions; gather
            # results of the previous phase are consumed directly in padded
            # layout — the drain of layer l fuses into layer l+1's dst work)
            if phase.dst.store_ids:
                denv = eval_vertex(safe_pad_ids, phase.dst.nodes, padded=True)
                for nid in phase.dst.store_ids:
                    vstore[nid] = unpad(denv[nid])
            if not phase.has_tile_work:
                continue

            scan_gathers = phase.scan_gathers()

            # ---- accumulators (shared across all buckets of this phase)
            acc = _init_gather_acc(scan_gathers, P, dmax)

            # ---- kernel-dispatched gather blocks
            for g in phase.kernel_gathers():
                if g.kernel == S.KERNEL_SEGMENT_SOFTMAX:
                    xs0 = with_dst(ta0)

                    def tile_se(xs):
                        senv = eval_vertex(xs["src_ids"], phase.src.nodes)
                        _, elookup = edge_env(g.edge_nodes, xs, senv)
                        h = src_value(senv, g.src_value_id, xs["src_ids"])
                        return elookup(g.score_id)[:, 0], h[xs["edge_src"]]

                    scores_e, vals = jax.vmap(tile_se)(xs0)    # (T,E), (T,E,F)
                    if self.layout == "csr":
                        # per-edge scores/vals feed the kernel directly: the
                        # row-pointer walk replaces the densify pass
                        out = self.softmax_csr_kernel(
                            ta0["row_ptr"], scores_e, vals, ta0["part_id"],
                            kc0["flags"], n_parts=P)
                    else:
                        scores = densify_edge_scores(
                            scores_e, ta0["edge_dst"], ta0["n_edge"], dmax=dmax)
                        out = self.softmax_kernel(scores, vals, ta0["part_id"],
                                                  kc0["flags"], n_parts=P)
                    out = jnp.where(kc0["pmask"][:, None, None] > 0, out, 0.0)
                    publish_gather(g.acc.recv_id, out)
                    continue

                # SpMM variants: one densified kernel call per size bucket,
                # partition outputs summed into a shared (P, Dmax, F) buffer
                total = jnp.zeros((P, dmax, g.acc.dim), jnp.float32)
                for ta, kc in zip(tas, kcs):
                    senv = eval_vertex(ta["src_ids"], phase.src.nodes)
                    xsrc = src_value(senv, g.src_value_id, ta["src_ids"])
                    if self.layout == "csr":
                        if g.kernel == S.KERNEL_SPMM:
                            w = jnp.ones(ta["edge_src"].shape, jnp.float32)
                        else:
                            xs_b = with_dst(ta)

                            def tile_w(xs):
                                senv_t = eval_vertex(xs["src_ids"],
                                                     phase.src.nodes)
                                _, elookup = edge_env(g.edge_nodes, xs, senv_t)
                                return elookup(g.weight_id)[:, 0]

                            w = jax.vmap(tile_w)(xs_b)         # (T, E)
                            # zero padded slots: they are unreachable via the
                            # row pointers but must not inject inf/NaN
                            emask = (jnp.arange(w.shape[1])[None, :]
                                     < ta["n_edge"][:, None])
                            w = jnp.where(emask, w, 0.0)
                        out = self.csr_kernel(ta["row_ptr"], ta["edge_src"],
                                              w, xsrc, ta["part_id"],
                                              kc["flags"], n_parts=P)
                    else:
                        if g.kernel == S.KERNEL_SPMM:
                            adj = kc["adj"]
                        else:    # weighted: densify the runtime edge weights
                            xs_b = with_dst(ta)

                            def tile_w(xs):
                                senv_t = eval_vertex(xs["src_ids"],
                                                     phase.src.nodes)
                                _, elookup = edge_env(g.edge_nodes, xs, senv_t)
                                return elookup(g.weight_id)[:, 0]

                            w = jax.vmap(tile_w)(xs_b)         # (T, E)
                            adj = densify_edge_weights(
                                w, ta["edge_dst"], ta["edge_src"], ta["n_edge"],
                                dmax=dmax, smax=int(ta["src_ids"].shape[1]))
                        out = self.tile_kernel(adj, xsrc, ta["part_id"],
                                               kc["flags"], n_parts=P)
                    # partitions with no tile in this bucket are never
                    # written by the kernel (uninitialized, may be NaN)
                    total = total + jnp.where(kc["pmask"][:, None, None] > 0,
                                              out, 0.0)
                publish_gather(g.acc.recv_id, total)

            # ---- the pipelined tile loop, one scan per bucket
            if scan_gathers:
                def body(acc, xs):
                    emask = (jnp.arange(xs["edge_src"].shape[0])
                             < xs["n_edge"])[:, None]
                    pid = xs["part_id"]
                    senv = eval_vertex(xs["src_ids"], phase.src.nodes)
                    _, elookup = edge_env(phase.edge.nodes, xs, senv)
                    edst = xs["edge_dst"]
                    for g in scan_gathers:
                        _gather_accumulate(acc, g, elookup(g.acc.value_id),
                                           emask, edst, pid, dmax)
                    return acc, 0

                for ta in tas:
                    acc, _ = jax.lax.scan(body, acc, with_dst(ta))

                # ---- publish scan-gather results (padded layout; flat (V,)
                # store only when a tile-side path reads them)
                for g in scan_gathers:
                    publish_gather(g.acc.recv_id, _drain_gather_acc(acc, g))

        outs = [vstore[o] for o in sp.outputs]
        if perm is not None:
            outs = [o[perm["rank"]] for o in outs]
        return outs


def run_pipelined(compiled: C.CompiledGNN, graph: Graph, tiles,
                  inputs: Dict[str, Array], params: Dict[str, Array],
                  tile_kernel: Optional[Callable] = None,
                  kernel_dispatch: Optional[bool] = None,
                  reordering=None) -> List[Array]:
    return PipelinedRunner(compiled, graph, tiles, tile_kernel=tile_kernel,
                           kernel_dispatch=kernel_dispatch,
                           reordering=reordering)(inputs, params)


# ---------------------------------------------------------------------------
# sharded execution: one ScheduledProgram data-parallel over dst partitions
# ---------------------------------------------------------------------------

def _quantize_cap(n: int) -> int:
    """Round a per-shard tile capacity up to the next power of two (serving:
    small per-request variance in shard tile counts must map onto one
    compiled shape)."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def _shard_tile_counts(tiles, plan: ShardPlan) -> List[List[int]]:
    """Per bucket, per shard: number of real (n_edge > 0) tiles assigned."""
    buckets: List[TileSet] = (list(tiles.buckets)
                              if isinstance(tiles, BucketedTileSet) else [tiles])
    out = []
    for b in buckets:
        shard = plan.shard_of_part[b.part_id]
        real = b.n_edge > 0
        out.append([int(np.sum(real & (shard == k)))
                    for k in range(plan.n_shards)])
    return out


def _source_tileset(tiles) -> TileSet:
    return tiles.source if isinstance(tiles, BucketedTileSet) else tiles


def _shard_real_counts(ts: TileSet, plan: ShardPlan) -> List[int]:
    shard = plan.shard_of_part[ts.part_id]
    real = ts.n_edge > 0
    return [int(np.sum(real & (shard == k))) for k in range(plan.n_shards)]


def _exchange_cap(tiles, plan: ShardPlan, quantize_tile_cap: bool) -> int:
    """Static send-buffer capacity of the restricted boundary exchange:
    the largest per-shard send set (rows a shard owns that remote shards'
    gather blocks read), power-of-two quantized under serving's cap
    quantization so small per-request variance shares one compiled shape."""
    cap = max(1, exchange_sets(tiles, plan).max_send)
    return _quantize_cap(cap) if quantize_tile_cap else cap


def shard_layout_signature(tiles, n_devices: int, mode: str = "cost",
                           quantize_tile_cap: bool = False,
                           kernel_dispatch: bool = False,
                           kernels: Tuple[str, ...] = (),
                           model_axis: int = 1) -> Tuple:
    """Shape identity of the sharded execution layout — everything a
    :class:`ShardedRunner` compilation depends on beyond the program and
    tile-set signatures.  Cheap (pure numpy); the serving engine calls it
    per request to key the program cache, so two requests share a warm
    sharded runner iff their shard layouts realize identical shapes.

    ``kernel_dispatch`` (and, when it is on, the program's kernel tags) is
    part of the identity: a scan-scheduled compilation must never alias a
    kernel-dispatched one, and the segment-softmax kernel adds a per-shard
    capacity for the unbucketed tile batch that scan programs don't have.
    Multi-shard layouts append the restricted-exchange send capacity
    (:func:`_exchange_cap`); ``model_axis`` names the 2-D mesh's feature
    axis width — a different feature split never aliases."""
    plan = plan_shards(tiles, n_devices, mode=mode)
    caps = []
    for counts in _shard_tile_counts(tiles, plan):
        cap = max(1, max(counts))
        caps.append(_quantize_cap(cap) if quantize_tile_cap else cap)
    if kernel_dispatch and S.KERNEL_SEGMENT_SOFTMAX in kernels:
        cap0 = max(1, max(_shard_real_counts(_source_tileset(tiles), plan)))
        caps.append(_quantize_cap(cap0) if quantize_tile_cap else cap0)
    if n_devices > 1:
        caps.append(_exchange_cap(tiles, plan, quantize_tile_cap))
    return ("shardlayout", n_devices, mode, int(model_axis),
            plan.n_local_parts, tuple(caps), bool(kernel_dispatch))


def _shard_partition_ids(plan: ShardPlan, part_start: np.ndarray,
                         part_size: np.ndarray, dmax: int,
                         n_vertices: int) -> np.ndarray:
    """(K, P_loc, Dmax) global vertex id per (shard, local slot, offset);
    invalid slots carry the sentinel ``n_vertices``."""
    K, P_loc = plan.n_shards, plan.n_local_parts
    ids = np.full((K, P_loc, dmax), n_vertices, np.int32)
    for k, parts in enumerate(plan.parts_of_shard):
        for j, p in enumerate(parts):
            n = int(part_size[p])
            ids[k, j, :n] = int(part_start[p]) + np.arange(n, dtype=np.int32)
    return ids


def _shard_layout(tiles, plan: ShardPlan, quantize_tile_cap: bool,
                  kernels: frozenset = frozenset()
                  ) -> Tuple[Dict, Dict, Tuple]:
    """Build the per-device operand arrays for a sharded run.

    Returns ``(shard_ops, repl_ops, caps)``: ``shard_ops`` arrays carry a
    leading mesh axis (row ``k`` = shard ``k``'s slice), ``repl_ops`` are
    replicated tables.  Per bucket, each shard receives its partitions' real
    tiles in the bucket's partition-major order (bucket order preserved) and
    is padded to a common capacity with zero-edge filler rows the scan masks
    out.  Filler rows repeat the shard's last real ``part_id``/``local_pid``
    (:func:`~repro.core.tiling.pad_tileset`'s convention), so under the
    Pallas FIRST/LAST flag protocol they extend that partition's run with
    zero blocks instead of corrupting another partition's accumulator.

    When ``kernels`` names Pallas gather blocks, each bucket additionally
    carries the per-shard kernel constants — FIRST/LAST ``flags`` over the
    local-partition sequence, the local-slot presence mask ``pmask``, and
    (pure SpMM only) the stacked dense adjacency blocks ``adj`` — and a
    ``softmax`` entry lays out the *unbucketed* tile batch per shard for the
    segment-softmax kernel (online-softmax state cannot be merged across
    buckets).  All shapes are a pure function of the tile-set signature, the
    plan shape, and the caps — :meth:`ShardedRunner.bind` rebuilds them for
    any structurally-identical tile set.
    """
    from ..kernels.tile_spmm.kernel import tile_flags
    from ..kernels.tile_spmm.ops import densify_tiles

    buckets: List[TileSet] = (list(tiles.buckets)
                              if isinstance(tiles, BucketedTileSet) else [tiles])
    K, P_loc = plan.n_shards, plan.n_local_parts
    dmax = int(tiles.part_size.max())
    counts = _shard_tile_counts(tiles, plan)
    want_kernels = bool(kernels & set(S.PALLAS_KERNELS))

    def shard_stack(b: TileSet, cap: int, adj_np: Optional[np.ndarray]) -> Dict:
        shard = plan.shard_of_part[b.part_id]
        sel_of = [np.nonzero((shard == k) & (b.n_edge > 0))[0]
                  for k in range(K)]

        def stack(a: np.ndarray, fill=0) -> np.ndarray:
            out = np.full((K, cap) + a.shape[1:], fill, a.dtype)
            for k, sel in enumerate(sel_of):
                out[k, :len(sel)] = a[sel]
            return out

        ops = dict(
            src_ids=stack(b.src_ids), edge_src=stack(b.edge_src),
            edge_dst=stack(b.edge_dst), edge_gid=stack(b.edge_gid),
            n_edge=stack(b.n_edge), part_id=stack(b.part_id),
            local_pid=stack(plan.local_slot_of_part[b.part_id].astype(np.int32)),
        )
        if b.row_ptr is not None:
            # filler rows keep the all-zero pointer table: every CSR row run
            # is [0, 0), the correct empty-tile contribution
            ops["row_ptr"] = stack(b.row_ptr)
        # filler rows extend the last real partition run (see docstring)
        for k, sel in enumerate(sel_of):
            if 0 < len(sel) < cap:
                ops["part_id"][k, len(sel):] = ops["part_id"][k, len(sel) - 1]
                ops["local_pid"][k, len(sel):] = ops["local_pid"][k, len(sel) - 1]
        if want_kernels:
            flags = np.zeros((K, cap), np.int32)
            pmask = np.zeros((K, P_loc), np.float32)
            for k, sel in enumerate(sel_of):
                flags[k] = tile_flags(ops["local_pid"][k])
                pmask[k, ops["local_pid"][k, :len(sel)]] = 1.0
            ops["flags"] = flags
            ops["pmask"] = pmask
            if adj_np is not None:
                ops["adj"] = stack(adj_np)
        return ops

    bucket_ops = []
    caps = []
    for b, cnts in zip(buckets, counts):
        cap = max(1, max(cnts))
        if quantize_tile_cap:
            cap = _quantize_cap(cap)
        caps.append(cap)
        adj_np = densify_tiles(b)[0] if (want_kernels and
                                         S.KERNEL_SPMM in kernels and
                                         b.layout != "csr") else None
        bucket_ops.append(shard_stack(b, cap, adj_np))

    pad_ids = _shard_partition_ids(plan, tiles.part_start, tiles.part_size,
                                   dmax, tiles.n_vertices)
    shard_ops = {"pad_ids": pad_ids, "buckets": bucket_ops}
    if want_kernels and S.KERNEL_SEGMENT_SOFTMAX in kernels:
        st = _source_tileset(tiles)
        cap0 = max(1, max(_shard_real_counts(st, plan)))
        if quantize_tile_cap:
            cap0 = _quantize_cap(cap0)
        caps.append(cap0)
        shard_ops["softmax"] = shard_stack(st, cap0, None)
    repl_ops = {"full_pad_ids": pad_ids.reshape(-1).copy()}
    if K > 1:
        # restricted-exchange send sets: per shard, the flat local-buffer
        # slots of the rows it owns that remote shards' gather blocks read,
        # and the replicated global-id table the receive scatter uses
        # (sentinel n_vertices rows are dropped).  Interior boundary
        # publishes all-gather only this compacted buffer.
        ex = exchange_sets(tiles, plan)
        ecap = max(1, ex.max_send)
        if quantize_tile_cap:
            ecap = _quantize_cap(ecap)
        caps.append(ecap)
        part_start = np.asarray(tiles.part_start)
        send_slots = np.zeros((K, ecap), np.int32)
        send_ids = np.full((K, ecap), tiles.n_vertices, np.int32)
        for k, rows in enumerate(ex.send_rows):
            part = np.searchsorted(part_start, rows, side="right") - 1
            slots = (plan.local_slot_of_part[part].astype(np.int64) * dmax
                     + (rows - part_start[part]))
            send_slots[k, :len(rows)] = slots.astype(np.int32)
            send_ids[k, :len(rows)] = rows.astype(np.int32)
        shard_ops["send_slots"] = send_slots
        repl_ops["send_ids"] = send_ids.reshape(-1).copy()
    return shard_ops, repl_ops, tuple(caps)


class ShardedRunner:
    """Data-parallel execution of one :class:`~repro.core.schedule
    .ScheduledProgram` over a 1-D device mesh of ``n_devices`` shards.

    Each shard owns whole destination partitions (a :class:`~repro.core
    .tiling.ShardPlan`), so every gather accumulator and every drained
    partition-layout value stays device-local; the only cross-device
    dataflow is the layer-boundary read of drained source values, exchanged
    as ONE ``all_gather`` of the padded ``(P_loc, Dmax, F)`` layout per
    boundary (values read back through destination replicas — GAT's softmax
    ``recvDst`` statistics, for instance — never leave their device).

    ``kernel_dispatch`` selects the scheduled program variant exactly as in
    :class:`PipelinedRunner`: ``True`` routes pattern-matched gather blocks
    through the Pallas kernels *inside* ``shard_map`` — each shard runs its
    bucketed tile batch through ``pallas_spmm`` / ``pallas_spmm_weighted`` /
    ``pallas_segment_softmax`` with device-local partition slots
    (``n_parts = P_loc``), so kernel outputs land straight in the local
    pstore and the one-all-gather-per-layer-boundary exchange census is
    unchanged.  ``False`` (the default when no ``tile_kernel`` is given)
    interprets the pure multi-phase scan schedule; both variants are
    numerically conformant with the single-device engines.  On CPU, force a
    multi-device mesh with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* the
    first jax import.

    ``mode`` picks the partition assignment (``"cost"``: LPT-balanced padded
    edge cost; ``"mincut"``: LPT seed + deterministic KL-style refinement
    minimizing cross-shard source reads; ``"contiguous"``: even ranges —
    deterministic across requests, what serving uses),
    ``quantize_tile_cap=True`` rounds per-shard tile capacities to powers of
    two so structurally-similar requests share one compiled shape.

    Interior layer boundaries use a *neighbor-restricted* exchange: each
    shard all-gathers only its compacted send buffer — the rows remote
    shards' gather blocks actually read, a static per-shard set derived from
    the plan (:func:`~repro.core.tiling.exchange_sets`) — and scatters its
    own partitions' rows locally.  Only the final output drain (whose
    results must be replicated on every shard) ships the full padded
    layout.  :func:`~repro.core.analysis.hazards.verify_exchange` proves
    coverage statically.

    ``model_axis=M > 1`` grows the mesh to 2-D ``("shards", "model")`` over
    ``n_devices * M`` devices: compute is replicated over the model axis
    while every boundary exchange ships each rank's ``ceil(F / M)`` feature
    slice over the shards axis and reassembles full width with one tiled
    model-axis all-gather — for wide hidden dims the per-link payload
    shrinks by ``M``.

    Like :class:`PipelinedRunner`, compilation depends only on
    :attr:`signature`; :meth:`bind`/:meth:`run_with` re-derive operands
    for a different same-signature tile set through the warm compilation.
    """

    def __init__(self, compiled: C.CompiledGNN, graph: Graph, tiles,
                 n_devices: Optional[int] = None, *, mode: str = "cost",
                 quantize_tile_cap: bool = False,
                 devices: Optional[List] = None,
                 tile_kernel: Optional[Callable] = None,
                 kernel_dispatch: Optional[bool] = None,
                 reordering=None, model_axis: int = 1):
        from ..kernels.tile_spmm import ops as tops

        devices = list(devices) if devices is not None else list(jax.devices())
        if model_axis < 1:
            raise ValueError(f"model_axis must be >= 1, got {model_axis}")
        if n_devices is None:
            n_devices = max(1, len(devices) // model_axis)
        if n_devices * model_axis > len(devices):
            raise ValueError(
                f"n_devices={n_devices} x model_axis={model_axis} but only "
                f"{len(devices)} jax devices are visible; on CPU set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
                "importing jax")
        if kernel_dispatch is None:
            kernel_dispatch = tile_kernel is not None
        self.c = compiled
        self.kernel_dispatch = bool(kernel_dispatch)
        self.sp: S.ScheduledProgram = compiled.schedule(self.kernel_dispatch)
        self.graph = graph
        self.tiles = tiles
        self.layout = getattr(tiles, "layout", "coo")
        self.mode = mode
        self.quantize_tile_cap = quantize_tile_cap
        self.n_devices = n_devices
        self.model_axis = int(model_axis)
        self.tile_kernel = tile_kernel if tile_kernel is not None else tops.spmm
        self.csr_kernel = tops.spmm_csr
        self.softmax_kernel = tops.gat_aggregate
        self.softmax_csr_kernel = tops.gat_aggregate_csr
        # like PipelinedRunner: graph/tiles in reordered space, requests in
        # original ids; the (order, rank) permutation rides as a replicated
        # traced operand, so it adds no collective to the exchange census
        self.reordering = reordering
        self.reorder_mode = ("identity" if reordering is None
                             else reordering.mode)
        self._kernels = frozenset(g.kernel for ph in self.sp.phases
                                  for g in ph.gathers)
        self.plan = plan_shards(tiles, n_devices, mode=mode)
        self.dmax = int(tiles.part_size.max())
        self._ops_np, self._repl_np, self.caps = _shard_layout(
            tiles, self.plan, quantize_tile_cap, self._kernels)
        if reordering is not None and not reordering.is_identity:
            self._repl_np = dict(self._repl_np,
                                 order=reordering.order, rank=reordering.rank)
        self._publish = self._publish_ids()
        self._signature = ("sharded", n_devices, mode, self.plan.n_local_parts,
                           self.caps, self.kernel_dispatch,
                           self.sp.structure_signature(),
                           tiles.shape_signature(), self.reorder_mode,
                           self.model_axis)
        if self.model_axis > 1:
            grid = np.asarray(
                devices[:n_devices * self.model_axis]).reshape(
                    n_devices, self.model_axis)
            self.mesh = jax.sharding.Mesh(grid, ("shards", "model"))
        else:
            self.mesh = jax.sharding.Mesh(np.asarray(devices[:n_devices]),
                                          ("shards",))
        P = jax.sharding.PartitionSpec
        self._jitted = jax.jit(jax.shard_map(
            self._run, mesh=self.mesh,
            in_specs=(P(), P(), P("shards"), P()), out_specs=P(),
            check_vma=False))
        self._operands: Optional[Tuple] = None

    # ------------------------------------------------------------- identity
    @property
    def signature(self) -> Tuple:
        """(mesh, layout, program, tile-set) identity this compilation
        serves — includes ``n_devices`` so a serving cache can never alias a
        sharded program with a single-device one (or across mesh sizes)."""
        return self._signature

    def jit_cache_size(self) -> int:
        try:
            return int(self._jitted._cache_size())
        except AttributeError:
            return -1

    def _publish_ids(self) -> set:
        """Vertex node ids whose values must be exchanged into the
        replicated flat store: tile-side source reads (and the outputs) of
        values that are *gather-tainted* — transitively derived from a
        gather result, i.e. carrying partition-owned aggregated state.

        Untainted values (pure functions of replicated inputs, like GAT's
        ``h = x @ W``) are recomputed by the source replicas per tile —
        bitwise the same rows, no collective.  Values consumed only through
        destination replicas (``recvDst``) or later dst blocks stay
        device-local either way, so each layer boundary drains exactly one
        all-gather."""
        sp = self.sp
        node_op: Dict[int, str] = {}
        vnodes = []
        for seg in sp.prog.segments:
            for n in seg.nodes.values():
                node_op[n.id] = n.op
        for seg in sp.prog.vertex_segments():
            vnodes.extend(seg.toposort())
        tainted: set = set()
        for n in vnodes:
            if n.op == "recvInEdge" or any(i in tainted for i in n.inputs):
                tainted.add(n.id)

        reads = set(sp.outputs)
        for ph in sp.phases:
            for n in ph.src.nodes:
                reads.update(n.inputs)
            for g in ph.gathers:
                if g.src_value_id is not None:
                    reads.add(g.src_value_id)
        for rnid, vnid in sp.scatter_value_of.items():
            if node_op.get(rnid) == "recvSrc":
                reads.add(vnid)
        pub = (reads & tainted) | set(sp.outputs)
        return pub - {nid for nid, _ in sp.vertex_inputs}

    # ------------------------------------------------------------------ bind
    def bind(self, tiles, reordering=None) -> Tuple:
        """Device operands for a tile set structurally identical to the
        construction one (same tile-set signature AND same realized shard
        layout shapes) — the per-request rebind step of the serving cache.
        ``reordering`` must realize the runner's compiled reorder mode."""
        if tiles.shape_signature() != self.tiles.shape_signature():
            raise ValueError(
                "tile set is not structurally identical to this runner's: "
                f"{tiles.shape_signature()} != {self.tiles.shape_signature()}")
        _check_reorder_mode(self.reorder_mode, reordering)
        plan = plan_shards(tiles, self.n_devices, mode=self.mode)
        if plan.n_local_parts != self.plan.n_local_parts:
            raise ValueError(
                f"shard layout mismatch: {plan.n_local_parts} local "
                f"partition slots != {self.plan.n_local_parts}")
        ops, repl, caps = _shard_layout(tiles, plan, self.quantize_tile_cap,
                                        self._kernels)
        if caps != self.caps:
            raise ValueError(
                f"shard tile capacities changed: {caps} != {self.caps}")
        if reordering is not None and not reordering.is_identity:
            repl = dict(repl, order=reordering.order, rank=reordering.rank)
        return self._place(ops, repl)

    def _place(self, ops: Dict, repl: Dict) -> Tuple:
        """Put each shard's operand slice on its own device and the
        replicated tables on every device of the mesh."""
        spec = jax.sharding.PartitionSpec
        return (jax.device_put(ops, jax.sharding.NamedSharding(
                    self.mesh, spec("shards"))),
                jax.device_put(repl, jax.sharding.NamedSharding(
                    self.mesh, spec())))

    def _get_operands(self) -> Tuple:
        if self._operands is None:
            self._operands = self._place(self._ops_np, self._repl_np)
        return self._operands

    # ------------------------------------------------------------------ run
    def __call__(self, inputs: Dict[str, Array], params: Dict[str, Array],
                 operands: Optional[Tuple] = None) -> List[Array]:
        ops, repl = operands if operands is not None else self._get_operands()
        return self._jitted({k: jnp.asarray(v) for k, v in inputs.items()},
                            {k: jnp.asarray(v) for k, v in params.items()},
                            ops, repl)

    def run_with(self, tiles, inputs: Dict[str, Array],
                 params: Dict[str, Array], reordering=None) -> List[Array]:
        """Execute a different same-signature tile set through the warm
        compilation (no retrace: operand shapes identical by contract)."""
        return self(inputs, params, operands=self.bind(tiles, reordering))

    def lower(self, inputs: Dict[str, Array], params: Dict[str, Array]):
        """``jax.stages.Lowered`` of this runner's program on the
        construction tiles."""
        ops, repl = self._get_operands()
        return self._jitted.lower(
            {k: jnp.asarray(v) for k, v in inputs.items()},
            {k: jnp.asarray(v) for k, v in params.items()}, ops, repl)

    def lower_text(self, inputs: Dict[str, Array],
                   params: Dict[str, Array]) -> str:
        """Compiled HLO text (collective-census hook for tests/benchmarks)."""
        return self.lower(inputs, params).compile().as_text()

    # ---------------------------------------------------------- trace-time
    #: per-tile operand keys of the lax.scan body (kernel constants like
    #: ``pmask``/``adj`` ride in the same bucket dicts but must not be
    #: scanned over — their leading axis is not the tile capacity)
    _SCAN_KEYS = ("src_ids", "edge_src", "edge_dst", "edge_gid",
                  "n_edge", "part_id", "local_pid")

    def _run(self, inputs, params, ops, repl) -> List[Array]:
        from ..kernels.tile_spmm.ops import (densify_edge_scores,
                                             densify_edge_weights)

        sp = self.sp
        V = self.graph.n_vertices
        K, P_loc, dmax = self.n_devices, self.plan.n_local_parts, self.dmax
        pad_ids = ops["pad_ids"][0]                       # (P_loc, Dmax)
        pad_valid = (pad_ids < V)[..., None]
        safe_pad_ids = jnp.minimum(pad_ids, V - 1)
        full_ids = repl["full_pad_ids"]                   # (K*P_loc*Dmax,)
        part_start = jnp.asarray(self.tiles.part_start)   # (P,) by contract

        if "order" in repl:
            # replicated permutation of replicated inputs: no collective,
            # the per-layer all-gather census is unchanged
            inputs = dict(inputs)
            for name in {name for _, name in sp.vertex_inputs}:
                inputs[name] = inputs[name][repl["order"]]

        vstore: Dict[int, Array] = {nid: inputs[name]
                                    for nid, name in sp.vertex_inputs}
        estore: Dict[int, Array] = {nid: inputs[name]
                                    for nid, name in sp.edge_inputs}
        # device-local padded (P_loc, Dmax, F) stores: gather results and
        # dst-computed values (the drain pstore of the pipelined runner,
        # kept per shard)
        pstore: Dict[int, Array] = {}
        dstore: Dict[int, Array] = {}

        M = self.model_axis

        def mesh_gather(buf: Array) -> Array:
            """All-gather over the shards axis; under a 2-D mesh each model
            rank ships only its ceil(F / M) column slice and one tiled
            model-axis all-gather reassembles full width."""
            if M == 1:
                return jax.lax.all_gather(buf, "shards", axis=0)
            W = buf.shape[-1]
            wp = -(-W // M)
            pad = [(0, 0)] * (buf.ndim - 1) + [(0, wp * M - W)]
            bufp = jnp.pad(buf, pad)
            m = jax.lax.axis_index("model")
            chunk = jax.lax.dynamic_slice_in_dim(bufp, m * wp, wp, axis=-1)
            full = jax.lax.all_gather(chunk, "shards", axis=0)
            full = jax.lax.all_gather(full, "model", axis=full.ndim - 1,
                                      tiled=True)
            return full[..., :W]

        def publish(pending: Dict[int, Array]) -> None:
            """Exchange device-local padded values into the replicated flat
            (V, F) store: ONE shards-axis all-gather for everything this
            phase drains.  Interior boundaries ship only the compacted
            restricted send buffer (rows remote shards' gather blocks read)
            and scatter the shard's own rows locally; the final output
            drain — whose values must come out replicated — gathers the
            full padded layout."""
            if not pending:
                return
            ids = list(pending)
            widths = [int(pending[i].shape[-1]) for i in ids]
            buf = jnp.concatenate([pending[i] for i in ids], axis=-1)
            restricted = (K > 1 and "send_slots" in ops
                          and not (set(ids) & set(sp.outputs)))
            if restricted:
                flatbuf = buf.reshape(P_loc * dmax, -1)
                send = flatbuf[ops["send_slots"][0]]      # (C, F)
                full = mesh_gather(send)                  # (K, C, F)
                flat = full.reshape(full.shape[0] * full.shape[1], -1)
                store = jnp.zeros((V + 1, flat.shape[-1]), jnp.float32)
                store = store.at[repl["send_ids"]].set(flat)
                # own partitions' rows never ride the exchange: local scatter
                # (invalid padded slots carry the sentinel V and are dropped)
                store = store.at[pad_ids.reshape(-1)].set(flatbuf)[:V]
            else:
                buf = jnp.where(pad_valid, buf, 0.0)
                full = mesh_gather(buf)                   # (K,P_loc,Dmax,F)
                flat = full.reshape(K * P_loc * dmax, -1)
                store = jnp.zeros((V + 1, flat.shape[-1]), jnp.float32)
                store = store.at[full_ids].set(flat)[:V]
            off = 0
            for nid, w in zip(ids, widths):
                vstore[nid] = store[:, off:off + w]
                off += w

        def eval_vertex(rows, nodes, padded=False):
            env: Dict[int, Array] = {}

            def lookup(nid):
                if nid in env:
                    return env[nid]
                if padded:
                    if nid in pstore:
                        return pstore[nid]
                    if nid in dstore:
                        return dstore[nid]
                return vstore[nid][rows]

            for n in nodes:
                if n.id not in env and (n.id in vstore
                                        or (padded and n.id in dstore)):
                    continue        # drained earlier: read the stored value
                if n.op == "output":
                    env[n.id] = lookup(n.inputs[0])
                else:
                    env[n.id] = apply_compute(n.op, n.attrs, params,
                                              [lookup(i) for i in n.inputs])
            return env

        def edge_env(nodes, xs, senv):
            eenv: Dict[int, Array] = {}

            def elookup(nid):
                return eenv[nid] if nid in eenv else estore[nid][xs["edge_gid"]]

            for n in nodes:
                if n.op == "recvSrc":
                    src_nid = sp.scatter_value_of[n.id]
                    base = (senv[src_nid] if src_nid in senv
                            else vstore[src_nid][xs["src_ids"]])
                    eenv[n.id] = base[xs["edge_src"]]
                elif n.op == "recvDst":
                    src_nid = sp.scatter_value_of[n.id]
                    # destination replicas read their OWN partition's rows:
                    # device-local padded layout, no exchange
                    if src_nid in pstore:
                        eenv[n.id] = pstore[src_nid][xs["local_pid"]][xs["edge_dst"]]
                    elif src_nid in dstore:
                        eenv[n.id] = dstore[src_nid][xs["local_pid"]][xs["edge_dst"]]
                    else:
                        eenv[n.id] = vstore[src_nid][xs["dst_global"]]
                else:
                    eenv[n.id] = apply_compute(n.op, n.attrs, params,
                                               [elookup(i) for i in n.inputs])
            return eenv, elookup

        def src_value(senv, nid, rows):
            return senv[nid] if nid in senv else vstore[nid][rows]

        def local(ta, keys):
            """Strip the mesh axis off this shard's slice of ``ta`` and
            derive global destination rows from the partition table."""
            xs = {k: ta[k][0] for k in keys}
            xs["dst_global"] = jnp.minimum(
                part_start[xs["part_id"]][:, None] + xs["edge_dst"], V - 1)
            return xs

        for phase in sp.phases:
            # ---- destination block on the local partitions, then ONE
            # exchange of whatever this boundary drains to tile-side readers
            if phase.dst.store_ids:
                denv = eval_vertex(safe_pad_ids, phase.dst.nodes, padded=True)
                pending: Dict[int, Array] = {}
                for nid in phase.dst.store_ids:
                    dstore[nid] = denv[nid]
                    if nid in self._publish:
                        pending[nid] = denv[nid]
                publish(pending)
            if not phase.has_tile_work:
                continue

            scan_gathers = phase.scan_gathers()
            acc = _init_gather_acc(scan_gathers, P_loc, dmax)
            pending = {}

            def drain(g, val):
                """Gather result stays in the device-local padded store;
                queued for this phase's single exchange only when a
                tile-side path reads it."""
                pstore[g.acc.recv_id] = val
                if g.acc.recv_id in self._publish:
                    pending[g.acc.recv_id] = val

            # ---- kernel-dispatched gather blocks (device-local slots)
            for g in phase.kernel_gathers():
                if g.kernel == S.KERNEL_SEGMENT_SOFTMAX:
                    sm = ops["softmax"]
                    xs0 = local(sm, self._SCAN_KEYS)

                    def tile_se(xs):
                        senv = eval_vertex(xs["src_ids"], phase.src.nodes)
                        _, elookup = edge_env(g.edge_nodes, xs, senv)
                        h = src_value(senv, g.src_value_id, xs["src_ids"])
                        return elookup(g.score_id)[:, 0], h[xs["edge_src"]]

                    scores_e, vals = jax.vmap(tile_se)(xs0)
                    if self.layout == "csr":
                        out = self.softmax_csr_kernel(
                            sm["row_ptr"][0], scores_e, vals,
                            xs0["local_pid"], sm["flags"][0], n_parts=P_loc)
                    else:
                        scores = densify_edge_scores(
                            scores_e, xs0["edge_dst"], xs0["n_edge"], dmax=dmax)
                        out = self.softmax_kernel(scores, vals,
                                                  xs0["local_pid"],
                                                  sm["flags"][0],
                                                  n_parts=P_loc)
                    out = jnp.where(sm["pmask"][0][:, None, None] > 0,
                                    out, 0.0)
                    drain(g, out)
                    continue

                # SpMM variants: one densified kernel call per size bucket,
                # local-slot outputs summed into one (P_loc, Dmax, F) buffer
                total = jnp.zeros((P_loc, dmax, g.acc.dim), jnp.float32)
                for ta in ops["buckets"]:
                    xs = local(ta, self._SCAN_KEYS)
                    senv = eval_vertex(xs["src_ids"], phase.src.nodes)
                    xsrc = src_value(senv, g.src_value_id, xs["src_ids"])

                    def tile_w(x):
                        senv_t = eval_vertex(x["src_ids"], phase.src.nodes)
                        _, elookup = edge_env(g.edge_nodes, x, senv_t)
                        return elookup(g.weight_id)[:, 0]

                    if self.layout == "csr":
                        if g.kernel == S.KERNEL_SPMM:
                            w = jnp.ones(xs["edge_src"].shape, jnp.float32)
                        else:
                            w = jax.vmap(tile_w)(xs)
                            emask = (jnp.arange(w.shape[1])[None, :]
                                     < xs["n_edge"][:, None])
                            w = jnp.where(emask, w, 0.0)
                        out = self.csr_kernel(ta["row_ptr"][0],
                                              xs["edge_src"], w, xsrc,
                                              xs["local_pid"], ta["flags"][0],
                                              n_parts=P_loc)
                    else:
                        if g.kernel == S.KERNEL_SPMM:
                            adj = ta["adj"][0]
                        else:    # weighted: densify the runtime edge weights
                            w = jax.vmap(tile_w)(xs)
                            adj = densify_edge_weights(
                                w, xs["edge_dst"], xs["edge_src"], xs["n_edge"],
                                dmax=dmax, smax=int(xs["src_ids"].shape[1]))
                        out = self.tile_kernel(adj, xsrc, xs["local_pid"],
                                               ta["flags"][0], n_parts=P_loc)
                    # local slots with no tile in this bucket are never
                    # written by the kernel (uninitialized, may be NaN)
                    total = total + jnp.where(
                        ta["pmask"][0][:, None, None] > 0, out, 0.0)
                drain(g, total)

            # ---- the pipelined tile loop, one scan per bucket
            if scan_gathers:
                def body(acc, xs):
                    emask = (jnp.arange(xs["edge_src"].shape[0])
                             < xs["n_edge"])[:, None]
                    pid = xs["local_pid"]
                    senv = eval_vertex(xs["src_ids"], phase.src.nodes)
                    _, elookup = edge_env(phase.edge.nodes, xs, senv)
                    edst = xs["edge_dst"]
                    for g in scan_gathers:
                        _gather_accumulate(acc, g, elookup(g.acc.value_id),
                                           emask, edst, pid, dmax)
                    return acc, 0

                for ta in ops["buckets"]:
                    acc, _ = jax.lax.scan(body, acc,
                                          local(ta, self._SCAN_KEYS))
                for g in scan_gathers:
                    drain(g, _drain_gather_acc(acc, g))

            # everything this phase's gathers drain to tile-side readers
            # leaves in ONE collective (the static census counts on it)
            publish(pending)

        outs = [vstore[o] for o in sp.outputs]
        if "rank" in repl:
            outs = [o[repl["rank"]] for o in outs]
        return outs


def run_sharded(compiled: C.CompiledGNN, graph: Graph, tiles,
                inputs: Dict[str, Array], params: Dict[str, Array],
                n_devices: Optional[int] = None, mode: str = "cost",
                tile_kernel: Optional[Callable] = None,
                kernel_dispatch: Optional[bool] = None,
                reordering=None) -> List[Array]:
    return ShardedRunner(compiled, graph, tiles, n_devices, mode=mode,
                         tile_kernel=tile_kernel,
                         kernel_dispatch=kernel_dispatch,
                         reordering=reordering)(inputs, params)
