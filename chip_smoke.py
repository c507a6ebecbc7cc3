#!/usr/bin/env python3
"""Smoke test of the GNN path on a TPU: compiled Pallas kernels, the
full-graph runner and the serving front door, each checked against the
whole-graph reference.

    python chip_smoke.py              # one chip: full graph + serving
    python chip_smoke.py --chips 4    # four chips: the sharded path only

Phases (each passes or the script exits non-zero):

* device — JAX must report a TPU; there is no CPU fallback.
* full graph — ak2010 at its published size (45,293 V / 108,549 E), 2-layer
  gcn and gat at EMBED=128 through ``compile_gnn`` -> ``build_tiles`` ->
  ``PipelinedRunner(kernel_dispatch=True)`` on COO tiles, plus gcn on CSR
  tiles.  Every Pallas gather block must lower to a Mosaic kernel
  (``tpu_custom_call``), and every output must match ``run_reference``.
* serving — ``InferenceServer`` for gcn and gat, 4 requests of 16 graphs
  (~64 V / 256 E each); every graph's output must match the reference, and
  no request after the first may compile.
* sharded (``--chips 4`` only) — ``ShardedRunner`` (4 shards, min-cut plan)
  on the full-graph gcn, each shard on its own chip, compared with the
  one-chip ``PipelinedRunner``.

Graphs, weights and inputs are generated from ``SEED``.  Compile times and
warm ms per forward are informational.  The last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import compiler, executor, pipeline, tiling  # noqa: E402
from repro.gnn import graphs, models  # noqa: E402
from repro.serve import InferenceServer  # noqa: E402

SEED = 0
GRAPH, SCALE = "ak2010", 1.0
LAYERS = 2
#: ak2010 on a 32 x 32 grid has (Dmax, Smax, Emax) = (1416, 136, 144): every
#: tile kernel's double-buffered blocks fit the v5e's default scoped VMEM.
#: At 16 x 16 the (2831, 456) blocks do not (RESOURCE_EXHAUSTED vmem).
#: ``tests/test_tpu_compile.py`` compiles the four kernels at this grid.
FULL_GRID = 32
#: Every dot (kernels, dense transforms, reference) runs at
#: Precision.HIGHEST, i.e. f32-accurate on the MXU, so engine and reference
#: differ only in f32 summation order: about K * 2^-24 ~ 1e-5 of the
#: magnitudes summed for a K=128 contraction, per layer.  1e-4 of the
#: output's scale leaves a few-fold margin over two layers; a bf16 pass
#: (~4e-3) would not fit under it.
RTOL = 1e-4
SERVE_REQUESTS, SERVE_BATCH, SERVE_V, SERVE_E = 4, 16, 64, 256
WARM_REPEATS = 5
MOSAIC_OP = 'custom_call_target="tpu_custom_call"'
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class SmokeFailure(RuntimeError):
    pass


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def check_close(label: str, got, want) -> float:
    """Outputs are finite, of the reference's shape, and within ``RTOL`` of
    the reference's scale; returns the error relative to that scale."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    require(got.shape == want.shape,
            f"{label}: shape {got.shape} != reference {want.shape}")
    require(bool(np.isfinite(got).all()), f"{label}: non-finite output")
    scale = max(float(np.abs(want).max()), 1e-30)
    err = float(np.abs(got - want).max()) / scale
    require(err <= RTOL, f"{label}: max |out - ref| / max |ref| = {err:.3e} "
                         f"> {RTOL:g}")
    return err


def mosaic_calls(runner, inputs, params) -> int:
    """``tpu_custom_call`` ops (compiled Mosaic kernels) in the runner's
    optimized HLO, where every call site is its own op."""
    hlo = runner.lower(inputs, params).compile().as_text()
    return hlo.count(MOSAIC_OP)


def mosaic_blocks(runner, inputs, params) -> int:
    """Number of Pallas gather blocks in the runner's program; fails unless
    each one compiled to a Mosaic kernel."""
    blocks = sum(len(ph.kernel_gathers()) for ph in runner.sp.phases)
    calls = mosaic_calls(runner, inputs, params)
    require(blocks > 0, "the program dispatches no Pallas gather block")
    require(calls == blocks, f"{blocks} Pallas gather blocks but {calls} "
                             "tpu_custom_call ops in the compiled program")
    return blocks


def timed_forward(runner, inputs, params):
    """(outputs, first-run seconds, median warm ms per forward); run after
    the program was compiled, so the first run transfers and executes."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(runner(inputs, params))
    first_s = time.perf_counter() - t0
    warm = []
    for _ in range(WARM_REPEATS):
        t0 = time.perf_counter()
        out = jax.block_until_ready(runner(inputs, params))
        warm.append((time.perf_counter() - t0) * 1e3)
    return out, first_s, statistics.median(warm)


def describe(tiles, layout: str) -> str:
    T, D, S, E = (tiles.n_tiles, int(tiles.part_size.max()), tiles.s_max,
                  tiles.e_max)
    txt = (f"grid {tiles.n_dst_parts}x{tiles.n_src_parts} T={T} Dmax={D} "
           f"Smax={S} Emax={E}")
    if layout == "coo":
        return txt + f" dense adjacency {T * D * S * 4} B"
    return txt + f" row pointers {T * (D + 1) * 4} B"


def full_graph_phase(g, grid: int, label: str) -> None:
    for name, layout in (("gcn", "coo"), ("gat", "coo"), ("gcn", "csr")):
        tr = models.trace_stacked(name, LAYERS)
        c = compiler.compile_gnn(tr)
        params = models.init_params(tr, seed=SEED)
        inputs = models.init_inputs(tr, g, seed=SEED)
        tiles, _ = tiling.build_tiles(g, grid, grid, layout=layout)
        print(f"[full] {name}/{layout}: {describe(tiles, layout)}", flush=True)
        runner = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
        t0 = time.perf_counter()
        blocks = mosaic_blocks(runner, inputs, params)
        compile_s = time.perf_counter() - t0
        out, first_s, warm_ms = timed_forward(runner, inputs, params)
        ref = executor.run_reference(tr, g, inputs, params)
        require(len(out) == len(ref), f"{name}/{layout}: output count")
        errs = [check_close(f"{name}/{layout} output {i}", o, r)
                for i, (o, r) in enumerate(zip(out, ref))]
        print(f"[full] {name}/{layout}: {blocks} gather blocks compiled as "
              f"Mosaic kernels; max rel err {max(errs):.3e} (limit "
              f"{RTOL:g}); [{label}] bind+lower+compile {compile_s:.3f} s, "
              f"first run {first_s:.3f} s, warm {warm_ms:.3f} ms/forward",
              flush=True)


def serving_phase(label: str) -> None:
    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: events.append(event)
        if event in COMPILE_EVENTS else None)
    for name in ("gcn", "gat"):
        tr = models.trace_stacked(name, LAYERS)
        params = models.init_params(tr, seed=SEED)
        server = InferenceServer(name, params, n_layers=LAYERS)
        require(server.donate_inputs, "buffer donation is off on the chip")
        first_compiles = None
        for req in range(SERVE_REQUESTS):
            gs = [graphs.random_graph(SERVE_V, SERVE_E,
                                      seed=SEED + 1000 * req + k,
                                      model="powerlaw")
                  for k in range(SERVE_BATCH)]
            ins = [models.init_inputs(tr, gk, seed=SEED + 1000 * req + k)
                   for k, gk in enumerate(gs)]
            n_events = len(events)
            t0 = time.perf_counter()
            outs = server.submit(gs, ins)
            ms = (time.perf_counter() - t0) * 1e3
            compiled_now = len(events) - n_events
            if req == 0:
                first_compiles = server.compile_count
            else:
                require(compiled_now == 0 and
                        server.compile_count == first_compiles,
                        f"serve {name}: request {req} traced or compiled "
                        f"({compiled_now} events, {server.compile_count} "
                        f"runner builds)")
            # the reference runs on the disjoint union of the request's
            # graphs, which equals each graph's own result
            batch = graphs.batch_graphs(gs)
            merged = {k: np.concatenate([np.asarray(i[k]) for i in ins])
                      for k in ins[0]}
            ref = executor.run_reference(tr, batch.graph, merged, params)
            ref_g = [batch.unbatch_vertex(np.asarray(r)) for r in ref]
            err = max(check_close(f"serve {name} req {req} graph {k} out {o}",
                                  outs[k][o], ref_g[o][k])
                      for k in range(len(gs)) for o in range(len(ref)))
            print(f"[serve] {name} request {req}: {len(gs)} graphs, "
                  f"max rel err {err:.3e}; {compiled_now} compile events; "
                  f"[{label}] {ms:.3f} ms", flush=True)
        print(f"[serve] {name}: {server.compile_count} runner build(s), "
              f"none after the first request; stats {server.stats()}",
              flush=True)


def sharded_phase(g, grid: int, n_chips: int, label: str) -> None:
    require(len(jax.devices()) >= n_chips,
            f"--chips {n_chips} but JAX sees {len(jax.devices())} devices")
    tr = models.trace_stacked("gcn", LAYERS)
    c = compiler.compile_gnn(tr)
    params = models.init_params(tr, seed=SEED)
    inputs = models.init_inputs(tr, g, seed=SEED)
    tiles, _ = tiling.build_tiles(g, grid, grid)
    print(f"[sharded] gcn/coo: {describe(tiles, 'coo')}", flush=True)
    sharded = pipeline.ShardedRunner(c, g, tiles, n_devices=n_chips,
                                     kernel_dispatch=True, mode="mincut")
    ops, _ = sharded.bind(tiles)
    for key, arr in (("src_ids", ops["buckets"][0]["src_ids"]),
                     ("pad_ids", ops["pad_ids"])):
        homes = {s.device for s in arr.addressable_shards}
        require(len(homes) == n_chips and
                all(s.data.shape[0] == 1 for s in arr.addressable_shards),
                f"shard operand {key} is not split one slice per chip: "
                f"{[(s.device, s.data.shape) for s in arr.addressable_shards]}")
    t0 = time.perf_counter()
    calls = mosaic_calls(sharded, inputs, params)
    compile_s = time.perf_counter() - t0
    require(calls > 0, "sharded program has no Mosaic kernel")
    out, first_s, warm_ms = timed_forward(sharded, inputs, params)
    ran_on = {s.device for s in out[0].addressable_shards}
    require(len(ran_on) == n_chips, f"sharded output lives on {ran_on}")
    single = pipeline.PipelinedRunner(c, g, tiles, kernel_dispatch=True)
    one = jax.block_until_ready(single(inputs, params))
    errs = [check_close(f"sharded vs one-chip output {i}", a, b)
            for i, (a, b) in enumerate(zip(out, one))]
    plan = sharded.plan
    print(f"[sharded] gcn x{n_chips} (mincut, edge cut {plan.edge_cut()}): "
          f"{calls} Mosaic kernel calls, one operand slice per chip on "
          f"{sorted(str(d) for d in ran_on)}; max rel err vs one chip "
          f"{max(errs):.3e} (limit {RTOL:g}); [{label}] lower+compile "
          f"{compile_s:.3f} s, first run {first_s:.3f} s, warm "
          f"{warm_ms:.3f} ms/forward", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path on four chips")
    args = ap.parse_args(argv)

    cache = compile_cache.enable()
    devs = jax.devices()
    dev = devs[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__} compile cache={cache}",
          flush=True)
    require(dev.platform == "tpu",
            f"no TPU: JAX runs on {dev.platform!r}; this smoke test needs "
            "the chip")
    label = f"{dev.platform} {dev.device_kind}"

    g = graphs.paper_graph(GRAPH, SCALE, seed=SEED)
    print(f"[graph] {g.name}: {g.n_vertices} V, {g.n_edges} E", flush=True)
    if args.chips > 1:
        sharded_phase(g, FULL_GRID, args.chips, label)
    else:
        full_graph_phase(g, FULL_GRID, label)
        serving_phase(label)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        sys.exit(1)
