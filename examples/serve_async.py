"""Async serving walkthrough: continuous batching with deadlines and sheds.

    PYTHONPATH=src python examples/serve_async.py --requests 48

Builds on examples/serve_gnn.py (the synchronous engine) and drives the
async tier documented in docs/SERVING.md:

  1. register two tenants (gcn, gat) on one shared program cache, each
     with a per-tenant cache budget and a warmup set;
  2. start the server — canonical size classes compile in the background
     while requests are already being admitted;
  3. fire a burst of individual requests with deadlines and collect
     tickets; the scheduler forms batches per (model, size class);
  4. deliberately overload a tiny second server to show structured
     Overloaded results (no exceptions) under both shed policies;
  5. dump the metrics snapshot (p50/p99 latency, batch fill, sheds).
"""
import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.gnn import graphs, models
from repro.serve import AsyncInferenceServer, Overloaded


def make_requests(model, n, *, v, e, seed0=0):
    """n (graph, inputs) pairs for one tenant, same size class."""
    tr = models.trace_named(model)
    out = []
    for k in range(n):
        g = graphs.random_graph(
            v, e, seed=seed0 + k, model="powerlaw",
            n_edge_types=models.n_edge_types(tr))
        out.append((g, models.init_inputs(tr, g, seed=seed0 + k)))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48,
                    help="requests per tenant in the main burst")
    ap.add_argument("--vertices", type=int, default=48)
    ap.add_argument("--edges", type=int, default=192)
    ap.add_argument("--deadline", type=float, default=3.0,
                    help="per-request deadline; a trailing partial batch "
                         "ships when its slack hits dispatch_margin_s, so "
                         "this also bounds the burst's tail")
    args = ap.parse_args(argv)
    if args.requests < 1:
        ap.error("--requests must be >= 1")

    # -- 1+2: two tenants, shared cache, background warmup ------------------
    reqs = {m: make_requests(m, args.requests,
                             v=args.vertices, e=args.edges)
            for m in ("gcn", "gat")}
    srv = AsyncInferenceServer(max_queue=4 * args.requests,
                               default_deadline_s=args.deadline,
                               n_workers=2)
    for m in ("gcn", "gat"):
        srv.register_model(m, m, models.init_params(models.trace_named(m)),
                           max_batch=16, cache_budget=8,
                           warmup_graphs=[reqs[m][0][0]])

    with srv:                      # start(): scheduler + workers + warmup
        while not srv.warmup_done():
            time.sleep(0.05)
        print("warmup done:", srv.stats()["metrics"]["warmup"])

        # -- 3: a mixed burst of individual requests ------------------------
        t0 = time.perf_counter()
        tickets = [(m, srv.submit(g, ins, model=m))
                   for m in ("gcn", "gat") for g, ins in reqs[m]]
        ok = 0
        for m, t in tickets:
            res = t.result(timeout=60.0)
            if t.ok:
                ok += 1
                last = np.asarray(res)  # this request's vertex outputs
        dt = time.perf_counter() - t0
        n = len(tickets)
        print(f"burst: {ok}/{n} served in {dt * 1e3:.0f} ms "
              f"({n / dt:.0f} req/s), last output {last.shape}")

        snap = srv.stats()["metrics"]
        print(f"latency p50/p99: {snap['latency_s']['p50'] * 1e3:.1f}/"
              f"{snap['latency_s']['p99'] * 1e3:.1f} ms, "
              f"mean batch fill {snap['batch_fill']['mean']:.2f}, "
              f"sheds {snap['shed']}")
        print("shared cache:", srv.stats()["cache"])

    # -- 4: overload a tiny server to show structured shedding --------------
    for policy in ("reject-new", "drop-oldest"):
        tiny = AsyncInferenceServer(max_queue=4, shed_policy=policy,
                                    default_deadline_s=args.deadline)
        tiny.register_model("gcn", "gcn",
                            models.init_params(models.trace_named("gcn")),
                            max_batch=4)
        # not started: nothing drains, so admission fills then sheds
        tix = [tiny.submit(g, ins) for g, ins in reqs["gcn"][:8]]
        tiny.close(drain=False)
        shed = [t.result() for t in tix if not t.ok]
        reasons = sorted({s.reason for s in shed
                          if isinstance(s, Overloaded)})
        print(f"{policy:>11}: {len(shed)}/8 shed, reasons={reasons}")


if __name__ == "__main__":
    main()
