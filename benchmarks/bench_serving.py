"""Serving throughput: batched multi-graph inference vs the per-graph path.

The workload is the paper's target serving regime — a stream of many small
graphs (molecular / recommendation scale) — where the status-quo cost is one
fresh compilation per (model, graph).  The :class:`~repro.serve.engine.
InferenceServer` amortizes ONE compilation per structure class across the
whole stream and fills tiles by block-diagonal batching.

Measured per batch size {1, 16, 64}: graphs/sec over the stream (after a
one-batch warmup, i.e. steady-state serving) against the sequential baseline
(fresh ``PipelinedRunner`` per graph — compile included, because that is what
serving without the cache costs), plus program-cache behavior on the
repeated-signature stream: post-warmup hit rate and recompile count.

``--smoke`` shrinks the stream for CI and writes
``reports/bench_serving_smoke.json`` (full runs write
``reports/bench_serving.json``), so the perf trajectory is a build artifact
with per-PR smoke history kept distinct from full sweeps.
"""
from __future__ import annotations

import time

import jax

from repro.core import compiler, pipeline, tiling
from repro.gnn import graphs, models
from repro.serve import InferenceServer

from .common import fmt_table, write_report

BATCH_SIZES = (1, 16, 64)


def _workload(tr, n_graphs: int, v: int, e: int, name: str, seed0: int = 0):
    etypes = 3 if models.MODELS[name].needs_etype else None
    gs, ins = [], []
    for k in range(n_graphs):
        g = graphs.random_graph(v, e, seed=seed0 + k, model="powerlaw",
                                n_edge_types=etypes)
        gs.append(g)
        ins.append(models.init_inputs(tr, g, seed=seed0 + k))
    return gs, ins


def _sequential_gps(c, gs, ins, params, n_probe: int) -> float:
    """Status-quo path: a fresh runner (lower + jit) for every graph."""
    t0 = time.perf_counter()
    for g, inp in zip(gs[:n_probe], ins[:n_probe]):
        ts = tiling.grid_tile(g, 4, 4, sparse=True)
        out = pipeline.PipelinedRunner(c, g, ts, kernel_dispatch=True)(inp, params)
        jax.block_until_ready(out)
    return n_probe / (time.perf_counter() - t0)


def _batched_gps(server, gs, ins, batch: int) -> float:
    chunks = [(gs[i:i + batch], ins[i:i + batch])
              for i in range(0, len(gs), batch)]
    server.submit(*chunks[0])                      # warmup: compile the class
    t0 = time.perf_counter()
    for cg, ci in chunks:
        server.submit(cg, ci)
    return len(gs) / (time.perf_counter() - t0)


def run(smoke: bool = False):
    if smoke:
        model_names, n_graphs, v, e, n_probe = ("gcn",), 64, 48, 192, 3
    else:
        model_names, n_graphs, v, e, n_probe = ("gcn", "gat"), 192, 96, 420, 12

    rows, metrics = [], {}
    for name in model_names:
        tr = models.trace_named(name)
        c = compiler.compile_gnn(tr)
        params = models.init_params(tr)
        gs, ins = _workload(tr, n_graphs, v, e, name)

        seq_gps = _sequential_gps(c, gs, ins, params, n_probe)
        batched = {}
        cache_stats = {}
        for b in BATCH_SIZES:
            server = InferenceServer(c, params)
            gps = _batched_gps(server, gs, ins, b)
            batched[b] = gps
            st = server.cache.stats
            # the warmup submit is the only allowed compile; everything after
            # it must hit (requests counts one lookup per submitted batch)
            cache_stats[b] = dict(
                post_warmup_hit_rate=(st.hits / max(st.requests - 1, 1)),
                recompiles_after_warmup=st.compiles - 1,
                compiles=st.compiles)
            rows.append([name, b, f"{seq_gps:.1f}", f"{gps:.1f}",
                         f"{gps / seq_gps:.1f}x",
                         f"{cache_stats[b]['post_warmup_hit_rate']:.2f}",
                         cache_stats[b]["recompiles_after_warmup"]])
        metrics[name] = dict(seq_gps=seq_gps, batched_gps=batched,
                             speedup_b64=batched[64] / seq_gps,
                             cache=cache_stats)

    headers = ["model", "batch", "seq_g/s", "batched_g/s", "speedup",
               "hit_rate", "recompiles"]
    print("== serving throughput: batched + cached vs per-graph compile ==")
    print(fmt_table(rows, headers))

    tuned = tuned_reorder_stream(n_graphs=16 if smoke else 48)
    metrics["tuned_reorder"] = tuned
    print("\n== tuned CSR+degree route: steady-state recompiles "
          f"(gated) == {tuned}")

    write_report("bench_serving_smoke" if smoke else "bench_serving",
                 {"smoke": smoke,
                  "workload": dict(n_graphs=n_graphs, v=v, e=e),
                  "headers": headers, "rows": rows, "metrics": metrics})
    return metrics


def tuned_reorder_stream(n_graphs: int = 16):
    """Gated: a stream routed through a tuned CSR + degree-reorder config
    still converges to zero steady-state recompiles — the degree
    permutation is a traced operand rebound per request, never a new
    compilation, and the reorder/layout provenance in the cache key keeps
    the tuned route from aliasing the default one."""
    from repro.launch import autotune as AT
    from repro.serve.signature import quantize, size_class

    tr = models.trace_named("gcn")
    c = compiler.compile_gnn(tr)
    params = models.init_params(tr)
    gs, ins = _workload(tr, n_graphs, 120, 500, "gcn")

    cache = AT.TuneCache()
    class_key = (c.name, c.n_layers, size_class(gs[0]), quantize(1, floor=1))
    cache.put(AT.program_key(c), class_key,
              AT.TileConfig(4, 4, 2, 1, reorder="degree", layout="csr"))
    server = InferenceServer(c, params, tune_cache=cache)
    # warmup: first compile + monotone shape growth (the degree sort makes
    # the realized tile envelope vary per graph until headroom registers)
    n_warm = max(4, n_graphs // 4)
    for g, inp in zip(gs[:n_warm], ins[:n_warm]):
        server.submit([g], [inp])
    warm = server.compile_count
    for g, inp in zip(gs[n_warm:], ins[n_warm:]):
        server.submit([g], [inp])
    steady = server.compile_count - warm
    assert steady == 0, \
        f"tuned CSR+degree route recompiled {steady}x after warmup"
    return dict(warmup_compiles=warm, steady_state_recompiles=steady,
                graphs=n_graphs)


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small stream (CI smoke); still writes the report")
    args = ap.parse_args()
    run(smoke=args.smoke)
