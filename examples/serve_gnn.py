"""Serve a stream of small graphs through the batched inference engine.

    PYTHONPATH=src python examples/serve_gnn.py --model gcn --requests 6 --batch 16

Walkthrough of the serving layer (src/repro/serve/): each request batch of
small graphs is merged into one block-diagonal super-graph, padded onto a
size class, and executed by a cached jitted runner — one compilation per
*structure*, reused across every request of the stream.  Compare the first
(cold, compiling) request latency against the warm ones, then inspect the
program-cache counters.
"""
import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from repro.core import compiler
from repro.gnn import graphs, models
from repro.serve import InferenceServer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gcn", choices=sorted(models.MODELS))
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--vertices", type=int, default=64)
    ap.add_argument("--edges", type=int, default=256)
    args = ap.parse_args(argv)
    if args.requests < 1 or args.batch < 1:
        ap.error("--requests and --batch must be >= 1")

    tr = models.trace_named(args.model)
    compiled = compiler.compile_gnn(tr)
    params = models.init_params(tr)
    server = InferenceServer(compiled, params)

    print(f"serving {args.model}: {args.requests} requests x "
          f"{args.batch} graphs (~{args.vertices}V/{args.edges}E each)")
    for req in range(args.requests):
        gs, ins = [], []
        for k in range(args.batch):
            seed = req * 1000 + k
            g = graphs.random_graph(
                args.vertices, args.edges, seed=seed, model="powerlaw",
                n_edge_types=models.n_edge_types(tr))
            gs.append(g)
            ins.append(models.init_inputs(tr, g, seed=seed))
        t0 = time.perf_counter()
        outs = server.submit(gs, ins)
        dt = time.perf_counter() - t0
        tag = "cold (compiling)" if req == 0 else "warm (cache hit)"
        print(f"  request {req}: {args.batch} graphs in {dt * 1e3:7.1f} ms "
              f"({args.batch / dt:8.1f} g/s)  {tag}")

    # per-graph vertex outputs come back exactly sliced; pool one for show
    last = np.asarray(outs[0][0])
    print(f"graph 0 output: {last.shape}, mean readout "
          f"{float(last.mean()):+.4f}")
    print("server stats:", server.stats())


if __name__ == "__main__":
    main()
