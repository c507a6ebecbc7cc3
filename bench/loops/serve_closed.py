"""Serving streams of small graphs, one client in a closed loop.

Entry the window drives: ``InferenceServer.submit`` with the server's
defaults.  The request pool is generated in set-up; warm-up sends every
pool request once, in pool order, so every size class the window will meet
is registered and built before the window opens.  The window then sends the
pool in an order drawn from ``--seed``, cycling, and times each request
from when it was issued until its per-graph outputs are host arrays.  What
the check compares: every request completed in the window (grouping,
padding to the class shapes, the cached runner, the kernels and
unbatching), against the configuration's plain reference over the whole
pool.
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np

from bench import check, gen


class State:
    """What set-up built and the window drives."""


def setup(ctx) -> State:
    """Build the cell from its files and warm up its shapes."""
    from repro.gnn import graphs, models
    from repro.serve import InferenceServer

    cfg, tr = ctx.cfg, ctx.traffic
    s = State()
    s.ctx = ctx
    with ctx.step("molecule pool"):
        pool = gen.molecule_pool(tr)
        sizes = [[m[0] for m in req] for req in pool]
        flat = [m for req in pool for m in req]
        voff = np.concatenate([[0], np.cumsum([m[0] for m in flat])])
        s.n_vertices = int(voff[-1])
        s.src = np.concatenate([m[1] + voff[i] for i, m in enumerate(flat)])
        s.dst = np.concatenate([m[2] + voff[i] for i, m in enumerate(flat)])
        s.n_edges = len(s.src)
        s.graphs = [[graphs.Graph(src=m[1], dst=m[2], n_vertices=m[0])
                     for m in req] for req in pool]
        # request k owns pool vertices [s.rows[k], s.rows[k + 1])
        per_req = np.cumsum([0] + [len(r) for r in pool])
        s.rows = voff[per_req]
    ctx.log(f"[setup] pool: {len(pool)} requests x "
            f"{tr['molecules_per_request']} molecules, {s.n_vertices} atoms, "
            f"{s.n_edges} directed bonds, atoms per molecule mean "
            f"{np.mean([n for r in sizes for n in r]):.2f} max "
            f"{max(n for r in sizes for n in r)}")
    with ctx.step("model trace (parameter check)"):
        trace = models.trace_stacked(cfg["model"], cfg["layers"],
                                     cfg["in_dim"], cfg["hidden_dim"],
                                     cfg["out_dim"])
        ctx.require_params(trace.params)
    with ctx.step("weights and features (device)"):
        s.params, s.x = ctx.weights_and_features(s.n_vertices)
        jax.block_until_ready((s.params, s.x))
    with ctx.step("features to the client (host)"):
        feats = ctx.model.vertex_inputs(np.asarray(s.x), s.src, s.dst,
                                        s.n_vertices)
        s.inputs = [[{k: v[voff[j]:voff[j + 1]] for k, v in feats.items()}
                     for j in range(per_req[k], per_req[k + 1])]
                    for k in range(len(pool))]
    with ctx.step("server build"):
        s.server = InferenceServer(cfg["model"], s.params,
                                   n_layers=cfg["layers"])
    s.serve = lambda k: s.server.submit(s.graphs[k], s.inputs[k])
    with ctx.step("warm-up pass (builds every class)"):
        for k in range(len(pool)):
            s.serve(k)
    ctx.log(f"[setup] warm-up: {len(pool)} requests, "
            f"{s.server.compile_count} runner builds, stats "
            f"{s.server.stats()}")
    s.order = np.random.default_rng(ctx.subseed("order")).permutation(
        len(pool))
    s.cursor = 0
    return s


def window(s: State, seconds: float, annotate: bool = False) -> dict:
    """The closed loop for ``seconds``; ``annotate`` marks each iteration
    with a host span for the traced stretch."""
    lat, done, ends, failed, n_graphs = [], [], [], 0, 0
    st0 = s.server.stats()
    t0 = time.perf_counter()
    end = t0 + seconds
    while time.perf_counter() < end:
        k = int(s.order[s.cursor % len(s.order)])
        s.cursor += 1
        t_issue = time.perf_counter()
        try:
            if annotate:
                with jax.profiler.TraceAnnotation("bench.request"):
                    outs = s.serve(k)
            else:
                outs = s.serve(k)
        except Exception as e:      # a failed request counts, and is shown
            failed += 1
            print(f"request {k} failed: {e!r}", file=sys.stderr)
            continue
        ends.append(time.perf_counter())
        lat.append((ends[-1] - t_issue) * 1e3)
        done.append((k, outs))
        n_graphs += len(s.graphs[k])
    win = dict(n=len(done), attempted=len(done) + failed, failed=failed,
               seconds=(ends[-1] if ends else t0) - t0, t0=t0, ends=ends,
               latencies_ms=lat, graphs=n_graphs, results=done)
    st1 = s.server.stats()
    win["requests_delta"] = st1["requests"] - st0["requests"]
    win["batches_delta"] = st1["batches"] - st0["batches"]
    return win


def work(s: State) -> dict:
    """No per-forward work counts: no serving metric reads them."""
    return {}


def check_outputs(s: State, win: dict, limits: dict):
    """Free the program's state, run the reference, compare."""
    results = win.pop("results")
    s.serve = s.server = None                     # free the program's state
    ref = check.reference(s, "highest")
    err = check.MaxRelErr()
    for k, outs in results:
        r0, r1 = s.rows[k], s.rows[k + 1]
        if len(outs) != len(s.graphs[k]) or any(
                len(o) != len(ref) for o in outs):
            err.bad += 1
            continue
        for j, r in enumerate(ref):
            err.add(np.concatenate([o[j] for o in outs]), r[r0:r1])
    return check.checks(err, limits)


def _split(s: State, k: int, arrays):
    """Per-graph output lists of request ``k`` from pool-wide arrays."""
    r0 = s.rows[k]
    offs = r0 + np.cumsum([0] + [g.n_vertices for g in s.graphs[k]])
    return [[a[offs[j]:offs[j + 1]] for a in arrays]
            for j in range(len(s.graphs[k]))]


def control(s: State) -> None:
    """Put the reference, computed in three bf16 passes, in the program's
    place."""
    out = check.reference(s, "3pass")
    s.serve = lambda k: _split(s, k, out)


def fault(s: State, kind: str) -> None:
    """Break the timed path: ``half_batch`` leaves half of each request's
    graphs unanswered (zeros); ``altered_answer`` changes one answer of
    each request where it is produced."""
    serve = s.serve
    if kind == "half_batch":
        def broken(k):
            outs = serve(k)
            half = len(outs) // 2
            return outs[:half] + [[np.zeros_like(a) for a in o]
                                  for o in outs[half:]]
    elif kind == "altered_answer":
        def broken(k):
            outs = [list(o) for o in serve(k)]
            a = np.array(outs[0][0])
            a[0, 0] += 1e-2 * max(float(np.abs(a).max()), 1.0)
            outs[0][0] = a
            return outs
    else:
        raise ValueError(f"fault {kind!r} does not apply to this loop")
    s.serve = broken
