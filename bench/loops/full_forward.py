"""Full-graph inference, closed loop: one forward after another.

Entry the window drives: ``PipelinedRunner.__call__`` on the bound tiles,
with features, weights and tile operands already on the device.  Each
forward ends in ``block_until_ready``.  What the check compares: the first
and the last forward of the window (dense transforms, runtime densify, the
Pallas gather kernels and the unpad), against the configuration's plain
reference on the same graph, features and weights.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import check, gen


class State:
    """What set-up built and the window drives."""


def setup(ctx) -> State:
    """Build the cell from its files and warm up its shapes."""
    from repro.core import compiler, pipeline, tiling
    from repro.gnn import graphs, models

    cfg, tr = ctx.cfg, ctx.traffic
    s = State()
    s.ctx = ctx
    V = tr["vertices"]
    with ctx.step("graph"):
        s.src, s.dst = gen.geometric_graph(V, tr["undirected_edges"],
                                           tr["dataset_seed"])
        g = graphs.Graph(src=s.src, dst=s.dst, n_vertices=V, name=tr["name"])
    s.n_vertices, s.n_edges = V, len(s.src)
    with ctx.step("tiling"):
        tiles, _ = tiling.build_tiles(g, tr["grid"], tr["grid"],
                                      layout=tr["layout"])
    ctx.log(f"[setup] tiles: grid {tr['grid']}x{tr['grid']} "
            f"T={tiles.n_tiles} Dmax={int(tiles.part_size.max())} "
            f"Smax={tiles.s_max} Emax={tiles.e_max} layout={tr['layout']}")
    with ctx.step("program build"):
        trace = models.trace_stacked(cfg["model"], cfg["layers"],
                                     cfg["in_dim"], cfg["hidden_dim"],
                                     cfg["out_dim"])
        ctx.require_params(trace.params)
        s.runner = pipeline.PipelinedRunner(compiler.compile_gnn(trace), g,
                                            tiles, kernel_dispatch=True)
    with ctx.step("weights and features (device)"):
        s.params, s.x = ctx.weights_and_features(V)
        jax.block_until_ready((s.params, s.x))
    with ctx.step("transfer (graph inputs, tile operands)"):
        s.inputs = {k: jax.device_put(v) for k, v in
                    ctx.model.vertex_inputs(s.x, s.src, s.dst, V).items()}
        s.operands = s.runner.bind(tiles)
        jax.block_until_ready((s.inputs, s.operands))
    s.forward = lambda: s.runner(s.inputs, s.params, s.operands)
    with ctx.step("compile or cache load, first forward"):
        jax.block_until_ready(s.forward())
    with ctx.step("warm-up"):
        for _ in range(tr["warmup_forwards"]):
            jax.block_until_ready(s.forward())
    return s


def window(s: State, seconds: float, annotate: bool = False) -> dict:
    """The closed loop for ``seconds``; ``annotate`` marks each iteration
    with a host span for the traced stretch."""
    fwd = s.forward
    first = last = None
    ends = []
    t0 = time.perf_counter()
    end = t0 + seconds
    while not ends or ends[-1] < end:
        if annotate:
            with jax.profiler.TraceAnnotation("bench.forward"):
                out = jax.block_until_ready(fwd())
        else:
            out = jax.block_until_ready(fwd())
        if first is None:
            first = out
        last = out
        ends.append(time.perf_counter())
    n = len(ends)
    return dict(n=n, attempted=n, failed=0, seconds=ends[-1] - t0, t0=t0,
                ends=ends, outputs=[first, last])


def program_memory(s: State) -> dict:
    """What the compiler plans for the window's program, in bytes: its
    temporaries, arguments and outputs.  A compile-cache hit; run after
    the window, while the program's state is alive."""
    ma = s.runner.lower(s.inputs, s.params).compile().memory_analysis()
    return dict(temp=int(ma.temp_size_in_bytes),
                arguments=int(ma.argument_size_in_bytes),
                outputs=int(ma.output_size_in_bytes))


def work(s: State) -> dict:
    """Work of one forward from V, E and the layer equations alone."""
    m, cfg = s.ctx.model, s.ctx.cfg
    kf, kb = m.kernel_work(cfg, s.n_vertices, s.n_edges)
    return dict(model_flops=m.model_flops(cfg, s.n_vertices, s.n_edges),
                kernel_flops=kf, kernel_bytes=kb)


def check_outputs(s: State, win: dict, limits: dict):
    """Free the program's state, run the reference, compare."""
    got = [[np.asarray(o) for o in out] for out in win.pop("outputs")]
    s.forward = s.runner = s.operands = None      # free the program's state
    ref = check.reference(s, "highest")
    err = check.MaxRelErr()
    for out in got:
        if len(out) != len(ref):
            err.bad += 1
            continue
        for o, r in zip(out, ref):
            err.add(o, r)
    return check.checks(err, limits)


def control(s: State) -> None:
    """Put the reference, computed in three bf16 passes, in the program's
    place."""
    out = check.reference(s, "3pass")
    s.forward = lambda: out


def fault(s: State, kind: str) -> None:
    """Break the timed path: ``half_batch`` leaves half the vertices'
    answers out; ``altered_answer`` changes one answer where it is
    produced."""
    fwd = s.forward
    half = s.n_vertices // 2
    if kind == "half_batch":
        s.forward = lambda: [o.at[half:].set(0.0) for o in fwd()]
    elif kind == "altered_answer":
        s.forward = lambda: [o.at[half, 0].add(1e-2 * abs(o).max())
                             for o in fwd()]
    else:
        raise ValueError(f"fault {kind!r} does not apply to this loop")
