"""`InferenceServer` — the batched multi-graph serving front door.

``submit(graphs, inputs)`` serves a whole request batch through ONE
ScheduledProgram execution per size class:

1. group incoming graphs by :func:`~repro.serve.signature.size_class`;
2. per group, :func:`~repro.gnn.graphs.batch_graphs` merges the members into
   a block-diagonal super-graph, padded (vertices, edge-input rows, tile
   batch) onto the class's registered canonical shapes
   (:class:`~repro.serve.signature.ShapeRegistry`);
3. the structural signature keys the :class:`~repro.serve.cache.ProgramCache`
   — a hit reuses a warm jitted :class:`~repro.core.pipeline.PipelinedRunner`
   (``bind`` the tile operands, then call it: no retrace, no recompile);
4. merged outputs are sliced back into per-graph arrays.

Each stage runs inside a ``jax.profiler.TraceAnnotation`` span named
``serve.<stage>`` that carries the request's id (docs/SERVING.md,
"Tracing"); with no trace running a span costs about a microsecond.

Request padding is pure overhead the quantization keeps bounded (< 2x rows
worst case); compilation cost is amortized across every request of a class.
"""
from __future__ import annotations

import itertools
import threading
from typing import Dict, List, Optional, Sequence, Union

import numpy as np
from jax.profiler import TraceAnnotation

from ..core import compiler as C
from ..core import schedule as S
from ..core.pipeline import (PipelinedRunner, ShardedRunner, host_leaves,
                             shard_layout_signature)
from ..gnn import models as M
from ..gnn.graphs import Graph, batch_graphs
from .cache import ProgramCache
from .signature import (ShapeRegistry, quantize, size_class,
                        structure_signature)

Array = np.ndarray

#: process-wide request ids: the ``request`` argument of every serving span,
#: which ties a request's spans together across threads and engines
_REQUEST_IDS = itertools.count()


def _count_h2d(span: TraceAnnotation, arrays) -> None:
    """Put the number and bytes of ``arrays`` (what a stage hands to the
    device) on ``span``."""
    span.set_metadata(arrays=len(arrays),
                      bytes=int(sum(a.nbytes for a in arrays)))


def _pad_rows(arr: Array, rows: int) -> Array:
    arr = np.asarray(arr)
    if arr.shape[0] == rows:
        return arr
    out = np.zeros((rows,) + arr.shape[1:], arr.dtype)
    out[: arr.shape[0]] = arr
    return out


class InferenceServer:
    """Serve streams of small graphs through cached compiled programs.

    ``model`` may be a registered model name (``repro.gnn.models.MODELS``) or
    a pre-compiled :class:`~repro.core.compiler.CompiledGNN`; ``params`` set
    here are the default weights for every request.  ``donate_inputs=None``
    auto-enables XLA buffer donation for the per-request padded arrays on
    accelerator backends (donation is a no-op warning on CPU).

    ``shard_devices=N`` routes *large* size classes — padded vertex count >=
    ``shard_min_vertices`` — through a data-parallel
    :class:`~repro.core.pipeline.ShardedRunner` over an N-device mesh
    (contiguous partition assignment + power-of-two per-shard tile caps, so
    structurally-similar requests share one compiled shape).  The cache key
    then carries the device count, the realized shard layout, and the
    ``kernel_dispatch`` flag: a sharded program can never alias a
    single-device one, a different mesh size, or a scan-scheduled variant.
    Both routes honor ``kernel_dispatch`` — sharded requests run the Pallas
    gather blocks inside ``shard_map`` when it is on.

    ``tune_cache`` (a :class:`~repro.launch.autotune.TuneCache`) routes size
    classes with a tuned entry onto the tuned tile config: the tuned grid,
    vertex reorder, and edge layout replace the
    :func:`~repro.serve.signature.serving_grid` defaults, the canonical
    tile batch is size-bucketed with registry-managed per-bucket caps
    (monotone growth, so bucketed shapes converge instead of flaking at
    power-of-two boundaries), and the tuned shard count caps the mesh
    size.  Tuned and default registrations/cache keys never alias — both
    carry the tuned config key, including its reorder/layout fields.
    """

    def __init__(self, model: Union[str, C.CompiledGNN],
                 params: Optional[Dict[str, Array]] = None, *,
                 n_layers: int = 1, kernel_dispatch: bool = True,
                 cache_capacity: int = 32, target_part: int = 256,
                 donate_inputs: Optional[bool] = None,
                 shard_devices: Optional[int] = None,
                 shard_min_vertices: int = 2048,
                 shard_model_axis: int = 1,
                 tune_cache=None,
                 cache: Optional[ProgramCache] = None,
                 shapes: Optional[ShapeRegistry] = None,
                 cache_owner: Optional[str] = None):
        """Build a server around one compiled model.

        Args:
            model: registered model name or a pre-compiled
                :class:`~repro.core.compiler.CompiledGNN`.
            params: default weights for every request (a request may
                override them).
            n_layers: stack depth when ``model`` is a name; must agree with
                a pre-compiled model's layer count.
            kernel_dispatch: run Pallas gather kernels (else the scan
                schedule).
            cache_capacity: LRU capacity when no shared ``cache`` is given.
            target_part: vertices per destination partition for the
                default serving grid.
            donate_inputs: XLA buffer donation for padded request arrays
                (``None`` auto-enables off-CPU).
            shard_devices: route large classes over an N-device mesh.
            shard_min_vertices: padded-vertex threshold for the sharded
                route.
            shard_model_axis: feature-axis width of the sharded route's
                2-D ``("shards", "model")`` mesh — ``M > 1`` splits each
                boundary exchange into per-rank ``ceil(F / M)`` column
                slices over ``shard_devices * M`` devices (wide hidden
                dims); part of the cache key, so different splits never
                alias.
            tune_cache: optional :class:`~repro.launch.autotune.TuneCache`
                routing tuned classes onto tuned tile configs.
            cache: a shared :class:`ProgramCache` (multi-tenant serving);
                defaults to a private cache of ``cache_capacity``.
            shapes: a shared :class:`ShapeRegistry`; defaults to private.
            cache_owner: tenant tag for per-owner cache budgets; defaults
                to the compiled model's name.

        Raises:
            ValueError: on a layer-count conflict or an unrealizable
                ``shard_devices``.
        """
        if isinstance(model, str):
            self.compiled = C.compile_gnn(
                M.trace_named(model) if n_layers == 1
                else M.trace_stacked(model, n_layers))
        else:
            if n_layers != 1 and n_layers != model.n_layers:
                raise ValueError(
                    f"n_layers={n_layers} conflicts with the pre-compiled "
                    f"model's {model.n_layers} layers")
            self.compiled = model
        self.params = params
        self.kernel_dispatch = kernel_dispatch
        self.target_part = target_part
        if donate_inputs is None:
            import jax
            donate_inputs = jax.default_backend() != "cpu"
        self.donate_inputs = donate_inputs
        if shard_model_axis < 1:
            raise ValueError(
                f"shard_model_axis must be >= 1, got {shard_model_axis}")
        if shard_devices is not None:
            import jax
            if shard_devices < 1:
                raise ValueError(
                    f"shard_devices must be >= 1, got {shard_devices}")
            # fail at configuration time, not when the first large batch
            # arrives hours into a serving session
            if shard_devices * shard_model_axis > len(jax.devices()):
                raise ValueError(
                    f"shard_devices={shard_devices} x model_axis="
                    f"{shard_model_axis} but only "
                    f"{len(jax.devices())} jax devices are visible; on CPU "
                    "set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    "before importing jax")
        self.shard_devices = shard_devices
        self.shard_min_vertices = shard_min_vertices
        self.shard_model_axis = shard_model_axis
        self.tune_cache = tune_cache
        sp = self.compiled.schedule(self.kernel_dispatch)
        self._kernel_tags = tuple(sorted(
            {g.kernel for ph in sp.phases for g in ph.gathers}
            - {S.KERNEL_SCAN}))
        self.cache = cache if cache is not None \
            else ProgramCache(capacity=cache_capacity)
        self.shapes = shapes if shapes is not None \
            else ShapeRegistry(target_part=target_part)
        self.cache_owner = (cache_owner if cache_owner is not None
                            else self.compiled.name)
        self._stats_lock = threading.Lock()
        self._requests = 0
        self._graphs_served = 0
        self._batches_run = 0
        self._sharded_batches = 0

    # ------------------------------------------------------------------ API
    def submit(self, graphs: Sequence[Graph],
               inputs: Sequence[Dict[str, Array]],
               params: Optional[Dict[str, Array]] = None
               ) -> List[List[Array]]:
        """Run the model over every graph; returns per-graph output lists
        (vertex-space arrays, same order as the model's declared outputs)."""
        if len(graphs) != len(inputs):
            raise ValueError(f"{len(graphs)} graphs but {len(inputs)} inputs")
        if not graphs:
            return []
        params = params if params is not None else self.params
        if params is None:
            raise ValueError("no params bound to the server or the request")

        rid = next(_REQUEST_IDS)
        with TraceAnnotation("serve.submit", request=rid,
                             graphs=len(graphs)) as span:
            with TraceAnnotation("serve.group", request=rid):
                groups: Dict[tuple, List[int]] = {}
                for i, g in enumerate(graphs):
                    groups.setdefault(size_class(g), []).append(i)
            span.set_metadata(groups=len(groups))

            results: List[Optional[List[Array]]] = [None] * len(graphs)
            for idxs in groups.values():
                outs = self._run_group([graphs[i] for i in idxs],
                                       [inputs[i] for i in idxs], params, rid)
                for i, out in zip(idxs, outs):
                    results[i] = out
        with self._stats_lock:
            self._requests += 1
            self._graphs_served += len(graphs)
        return results  # fully populated: every index belongs to one group

    def stats(self) -> Dict:
        """Serving counters: requests/graphs/batches served, cache size and
        hit/miss/compile counts, layer count, sharded-batch count."""
        return dict(requests=self._requests, graphs=self._graphs_served,
                    batches=self._batches_run, cache_size=len(self.cache),
                    n_layers=self.compiled.n_layers,
                    sharded_batches=self._sharded_batches,
                    cache=self.cache.stats.as_dict())

    @property
    def compile_count(self) -> int:
        """Total runner compilations so far (flat after warmup on a
        repeated-signature stream)."""
        return self.cache.stats.compiles

    @property
    def cache_hits(self) -> int:
        """Request batches served by a warm compiled runner."""
        return self.cache.stats.hits

    @property
    def cache_misses(self) -> int:
        """Request batches that had to build (and compile) a runner."""
        return self.cache.stats.misses

    # ------------------------------------------------------------ internals
    def _run_group(self, graphs: List[Graph],
                   inputs: List[Dict[str, Array]],
                   params: Dict[str, Array], rid: int) -> List[List[Array]]:
        with TraceAnnotation("serve.run_group", request=rid,
                             graphs=len(graphs)):
            with TraceAnnotation("serve.merge", request=rid):
                batch = batch_graphs(graphs)
            with TraceAnnotation("serve.canonical", request=rid):
                V_pad, tiles, E_pad, ro, tuned, tuned_key = \
                    self._canonical(graphs, batch)
            with TraceAnnotation("serve.inputs", request=rid):
                sp = self.compiled.schedule(self.kernel_dispatch)
                merged_inputs: Dict[str, Array] = {}
                for _, name in sp.vertex_inputs:
                    merged_inputs[name] = _pad_rows(np.concatenate(
                        [np.asarray(inp[name]) for inp in inputs]), V_pad)
                for _, name in sp.edge_inputs:
                    merged_inputs[name] = _pad_rows(np.concatenate(
                        [np.asarray(inp[name]) for inp in inputs]), E_pad)
            with TraceAnnotation("serve.lookup", request=rid) as span:
                runner, hit = self._runner(tiles, E_pad, ro, tuned,
                                           tuned_key, V_pad)
                span.set_metadata(hit=int(hit))
            with TraceAnnotation("serve.bind", request=rid) as span:
                operands = runner.bind(tiles, reordering=ro)
                if span.is_enabled():
                    _count_h2d(span, host_leaves(operands))
            with TraceAnnotation("serve.dispatch", request=rid) as span:
                if span.is_enabled():
                    _count_h2d(span, [a for a in (*merged_inputs.values(),
                                                  *params.values())
                                      if isinstance(a, np.ndarray)])
                outs = runner(merged_inputs, params, operands)
            with self._stats_lock:
                self._batches_run += 1

            with TraceAnnotation("serve.fetch", request=rid):
                V_real = batch.graph.n_vertices
                per_output = [batch.unbatch_vertex(np.asarray(o)[:V_real])
                              for o in outs]
                return [[per_output[o][g] for o in range(len(per_output))]
                        for g in range(len(graphs))]

    def _canonical(self, graphs: List[Graph], batch):
        """The class's padded vertex count, canonical tiles, padded edge
        rows, vertex reordering, and tuned config and key (``None`` and
        ``()`` on the default route)."""
        # class keys carry the program identity (name + layer count): shape
        # registrations of a 1-layer and a 2-layer program of the same model
        # must never alias, even if two servers share a registry
        class_key = (self.compiled.name, self.compiled.n_layers,
                     size_class(graphs[0]), quantize(len(graphs), floor=1))
        tuned = None
        if self.tune_cache is not None:
            from ..launch.autotune import program_key
            tuned = self.tune_cache.get(
                program_key(self.compiled, self.kernel_dispatch), class_key)
        if tuned is not None:
            # tuned route: tuned grid + reorder + edge layout +
            # size-bucketed tile batch; the registration key carries the
            # config (reorder/layout included) so default and tuned
            # canonical shapes of one class never alias
            tuned_key = ("tuned",) + tuned.key()
            merged_graph, tiles, E_pad, ro = self.shapes.canonical(
                class_key + (tuned_key,), batch.graph,
                grid=(tuned.n_dst_parts, tuned.n_src_parts),
                reorder=tuned.reorder, layout=tuned.layout,
                n_buckets=tuned.n_buckets)
        else:
            tuned_key = ()
            merged_graph, tiles, E_pad, ro = self.shapes.canonical(
                class_key, batch.graph)
        return merged_graph.n_vertices, tiles, E_pad, ro, tuned, tuned_key

    def _runner(self, tiles, E_pad: int, ro, tuned, tuned_key, V_pad: int):
        """(runner, hit): the cached runner of the structure signature, built
        on a miss."""
        n_dev = (self.shard_devices
                 if self.shard_devices and self.shard_devices > 1
                 and V_pad >= self.shard_min_vertices else 1)
        if tuned is not None and n_dev > 1:
            # the tuned shard count caps (never raises) the mesh size
            n_dev = max(1, min(n_dev, tuned.n_shards))
        key = structure_signature(self.compiled, tiles, E_pad,
                                  self.kernel_dispatch, reorder=ro.mode)
        built = []
        if n_dev > 1:
            # sharded route over an n_dev mesh, kernel dispatch honored
            # inside shard_map; key carries the mesh size, the realized
            # shard layout, the dispatch flag, the reorder mode, and the
            # tuned config.  The runner holds the graph/tiles in reordered
            # vertex space; requests stay in original ids and the rebind
            # ships the permutation as a replicated traced operand.
            key += (shard_layout_signature(
                        tiles, n_dev, mode="contiguous",
                        quantize_tile_cap=True,
                        kernel_dispatch=self.kernel_dispatch,
                        kernels=self._kernel_tags,
                        model_axis=self.shard_model_axis),
                    tuned_key)

            def build():
                built.append(True)
                return ShardedRunner(self.compiled, ro.graph, tiles, n_dev,
                                     mode="contiguous", quantize_tile_cap=True,
                                     kernel_dispatch=self.kernel_dispatch,
                                     reordering=ro,
                                     model_axis=self.shard_model_axis)
        else:
            key += (tuned_key,)

            def build():
                built.append(True)
                return PipelinedRunner(self.compiled, ro.graph, tiles,
                                       kernel_dispatch=self.kernel_dispatch,
                                       donate_inputs=self.donate_inputs,
                                       reordering=ro)
        runner = self.cache.get_or_build(key, build, owner=self.cache_owner)
        if n_dev > 1:
            with self._stats_lock:
                self._sharded_batches += 1
        return runner, not built
