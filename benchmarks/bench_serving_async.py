"""Async serving tier vs sequential per-request submit, under SLOs.

The workload is batch-forming load: a burst of individual graph requests
(one graph per request, each with a deadline) arrives faster than they can
be served one-by-one.  The sequential baseline is the status quo before the
async tier — a warm :class:`~repro.serve.engine.InferenceServer` driven one
``submit([g], [i])`` call per request (no batching; the cache is warm, so
this isolates the batching win from the compile-amortization win that
``bench_serving`` already measures).  The async tier
(:class:`~repro.serve.server.AsyncInferenceServer`) forms size-class
batches behind a request queue, pads partial batches onto canonical
shapes, and overlaps dispatch across a worker pool.

Asserted (the ISSUE 8 acceptance bar):

* async throughput >= 2x the sequential per-request baseline;
* zero steady-state recompiles (after background warmup, the program-cache
  compile counter is flat across the whole measured stream);
* p99 end-to-end latency bounded by the configured request deadline.

``--smoke`` shrinks the stream for CI; both modes write
``reports/bench_serving_async.json`` (the acceptance artifact) with the
p50/p99 latency, queue-depth, batch-fill, and shed metrics embedded.
"""
from __future__ import annotations

import time

from repro.core import compiler
from repro.gnn import graphs, models
from repro.serve import AsyncInferenceServer, InferenceServer

from .common import fmt_table, write_report


def _workload(tr, name: str, n: int, v: int, e: int, seed0: int = 0):
    etypes = 3 if models.MODELS[name].needs_etype else None
    gs, ins = [], []
    for k in range(n):
        g = graphs.random_graph(v, e, seed=seed0 + k, model="powerlaw",
                                n_edge_types=etypes)
        gs.append(g)
        ins.append(models.init_inputs(tr, g, seed=seed0 + k))
    return gs, ins


def _sequential_rps(compiled, params, gs, ins) -> float:
    """Warm per-request baseline: one submit([g], [i]) call per request."""
    server = InferenceServer(compiled, params)
    server.submit(gs[:1], ins[:1])                   # warm the class
    t0 = time.perf_counter()
    for g, inp in zip(gs, ins):
        server.submit([g], [inp])
    return len(gs) / (time.perf_counter() - t0)


def _async_rps(server, name, gs, ins, deadline_s):
    """Burst the whole stream at the async tier; returns (rps, tickets)."""
    t0 = time.perf_counter()
    tickets = server.submit_many(gs, ins, model=name, deadline_s=deadline_s)
    for t in tickets:
        t.result(timeout=deadline_s + 60)
    return len(gs) / (time.perf_counter() - t0), tickets


def run(smoke: bool = False):
    if smoke:
        model_names, n_requests, v, e = ("gcn",), 64, 48, 192
        max_batch, deadline_s = 16, 10.0
    else:
        model_names, n_requests, v, e = ("gcn", "gat"), 192, 96, 420
        max_batch, deadline_s = 16, 20.0

    server = AsyncInferenceServer(max_queue=4 * n_requests,
                                  n_workers=2,
                                  default_deadline_s=deadline_s,
                                  dispatch_margin_s=0.25)
    compiled, params, streams = {}, {}, {}
    for name in model_names:
        tr = models.trace_named(name)
        compiled[name] = compiler.compile_gnn(tr)
        params[name] = models.init_params(tr)
        streams[name] = _workload(tr, name, n_requests, v, e)
        warm_g = graphs.random_graph(
            v, e, seed=10_000, model="powerlaw",
            n_edge_types=3 if models.MODELS[name].needs_etype else None)
        server.register_model(name, compiled[name], params[name],
                              max_batch=max_batch, warmup_graphs=[warm_g])

    server.start()
    t_warm = time.perf_counter()
    while not server.warmup_done():                   # background warmup
        if time.perf_counter() - t_warm > 300:
            raise RuntimeError("warmup did not finish")
        time.sleep(0.02)
    warmup_s = time.perf_counter() - t_warm

    rows, metrics = [], {}
    for name in model_names:
        gs, ins = streams[name]
        # wall-clock CI gate: one re-measure absorbs scheduler jitter on a
        # noisy shared runner (the bar itself stays at the 2x acceptance)
        for attempt in range(2):
            seq_rps = _sequential_rps(compiled[name], params[name], gs, ins)
            compiles_before = server.cache.stats.compiles
            async_rps, tickets = _async_rps(server, name, gs, ins, deadline_s)
            recompiles = server.cache.stats.compiles - compiles_before
            served = sum(1 for t in tickets if t.ok)
            speedup = async_rps / seq_rps
            snap = server.metrics.snapshot()
            p50, p99 = snap["latency_s"]["p50"], snap["latency_s"]["p99"]
            checks = dict(
                speedup_ge_2x=speedup >= 2.0,
                zero_steady_state_recompiles=recompiles == 0,
                p99_within_deadline=p99 <= deadline_s,
                all_served=served == n_requests,
            )
            if all(checks.values()):
                break
        rows.append([name, f"{seq_rps:.1f}", f"{async_rps:.1f}",
                     f"{speedup:.1f}x", f"{p50 * 1e3:.1f}",
                     f"{p99 * 1e3:.1f}", recompiles,
                     snap["shed_total"],
                     f"{snap['batch_fill']['mean']:.2f}"])
        metrics[name] = dict(seq_rps=seq_rps, async_rps=async_rps,
                             speedup=speedup, served=served,
                             recompiles_steady_state=recompiles,
                             checks=checks)

    final = server.metrics.snapshot()
    server.close()

    headers = ["model", "seq_r/s", "async_r/s", "speedup", "p50_ms",
               "p99_ms", "recompiles", "shed", "fill"]
    print("== async serving tier vs sequential per-request submit ==")
    print(fmt_table(rows, headers))
    print(f"(background warmup {warmup_s:.1f}s; deadline {deadline_s}s; "
          f"batch cap {max_batch})")
    write_report("bench_serving_async",
                 {"smoke": smoke,
                  "workload": dict(n_requests=n_requests, v=v, e=e,
                                   max_batch=max_batch,
                                   deadline_s=deadline_s),
                  "warmup_s": warmup_s,
                  "headers": headers, "rows": rows,
                  "metrics": metrics,
                  "serve_metrics": final})
    for name, m in metrics.items():
        for check, passed in m["checks"].items():
            assert passed, (name, check, m)
    return metrics


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small stream (CI smoke); still writes the report")
    args = ap.parse_args()
    run(smoke=args.smoke)
