"""Knowledge-graph encoding, closed loop: one full-graph R-GCN forward after
another, as a link predictor re-encodes every entity before scoring.

Entry the window drives: ``PipelinedRunner.__call__`` on the bound tiles and
relation-grouped layout, with features, weights, edge inputs and operands
already on the device; each forward ends in ``block_until_ready`` (the
window, the program-memory count and the faults are ``full_forward``'s).
What the check compares: the window's first and last forward (the typed
aggregation on the relation kernel, the self transform, the ReLU) against
the configuration's plain reference on the same typed graph, features and
weights.

The program is built before any other step: a program that cannot take the
configuration's relation count and blocks stops the run there, before the
graph or the tiles are made.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from bench import check, gen_kg, manifest
from bench.manifest import BenchError

_FULL = manifest.load_module(manifest.BENCH / "loops" / "full_forward.py")
window = _FULL.window
program_memory = _FULL.program_memory
fault = _FULL.fault


class State:
    """What set-up built and the window drives."""


def build_program(cfg):
    """The configuration's traced and compiled program."""
    from repro.core import compiler
    from repro.gnn import models

    try:
        trace = models.trace_stacked(
            cfg["model"], cfg["layers"], cfg["in_dim"], cfg["hidden_dim"],
            cfg["out_dim"], n_types=cfg["relations"], n_blocks=cfg["blocks"])
    except TypeError as e:
        raise BenchError(f"the program cannot build {cfg['name']}: {e}")
    return trace, compiler.compile_gnn(trace)


def weights_and_features(ctx, n_vertices: int):
    """Weights (normal / sqrt(fan_in), fan-in a matrix's or a block's rows)
    and features (standard normal) drawn from ``--seed`` on the device."""
    import jax.numpy as jnp

    shapes = tuple((k, tuple(v)) for k, v in
                   sorted(ctx.cfg["params"].items()))
    dim = ctx.cfg["in_dim"]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes) + 1)
        params = {name: jax.random.normal(k, shape, jnp.float32)
                  / math.sqrt(shape[-2])
                  for k, (name, shape) in zip(keys[1:], shapes)}
        x = jax.random.normal(keys[0], (n_vertices, dim), jnp.float32)
        return params, x

    return make(jax.random.PRNGKey(ctx.subseed("weights")))


def setup(ctx) -> State:
    """Build the program, then the cell from its files; warm up."""
    from repro.core import pipeline, tiling
    from repro.gnn import graphs

    cfg, tr = ctx.cfg, ctx.traffic
    s = State()
    s.ctx = ctx
    with ctx.step("program build"):
        trace, compiled = build_program(cfg)
        ctx.require_params(trace.params)
    V = tr["entities"]
    with ctx.step("graph"):
        s.src, s.dst, s.etype = gen_kg.kg_graph(tr, log=ctx.log)
        g = graphs.Graph(src=s.src, dst=s.dst, n_vertices=V,
                         edge_type=s.etype, name=tr["name"])
    s.n_vertices, s.n_edges = V, len(s.src)
    with ctx.step("tiling"):
        tiles, _ = tiling.build_tiles(g, tr["grid"], tr["grid"],
                                      layout=tr["layout"])
    with ctx.step("runner and relation layout"):
        s.runner = pipeline.PipelinedRunner(compiled, g, tiles,
                                            kernel_dispatch=True)
    ctx.log(f"[setup] tiles: grid {tr['grid']}x{tr['grid']} "
            f"T={tiles.n_tiles} Emax={tiles.e_max}; relation layout "
            f"{s.runner.relation_rows}")
    with ctx.step("weights and features (device)"):
        s.params, s.x = weights_and_features(ctx, V)
        jax.block_until_ready((s.params, s.x))
    with ctx.step("transfer (graph inputs, tile operands)"):
        s.edge_in = ctx.model.edge_inputs(s.src, s.dst, s.etype, V)
        s.inputs = {k: jax.device_put(v)
                    for k, v in dict(s.edge_in, x=s.x).items()}
        s.operands = s.runner.bind(tiles)
        jax.block_until_ready((s.inputs, s.operands))
    s.relation_rows = dict(s.runner.relation_rows)
    s.forward = lambda: s.runner(s.inputs, s.params, s.operands)
    with ctx.step("compile or cache load, first forward"):
        jax.block_until_ready(s.forward())
    with ctx.step("warm-up"):
        for _ in range(tr["warmup_forwards"]):
            jax.block_until_ready(s.forward())
    return s


def work(s: State) -> dict:
    """Work of one forward from V, E and the layer equations alone, and the
    relation layout's rows."""
    m, cfg = s.ctx.model, s.ctx.cfg
    kf, kb = m.kernel_work(cfg, s.n_vertices, s.n_edges)
    return dict(model_flops=m.model_flops(cfg, s.n_vertices, s.n_edges),
                kernel_flops=kf, kernel_bytes=kb,
                rel_kernel_flops=kf, rel_kernel_bytes=kb,
                rel_real_rows=s.relation_rows["real_rows"],
                rel_padded_rows=s.relation_rows["padded_rows"],
                rel_groups=s.relation_rows["relation_groups"])


def reference(s: State, dot: str):
    """The configuration's plain reference over the whole typed graph on
    the run's features and weights, with the named dot: host arrays."""
    args = [jax.device_put(a) for a in
            (s.src, s.dst, s.etype, s.edge_in["rnorm"][:, 0])]
    f = jax.jit(lambda p, x, *a: s.ctx.model.forward(
        p, x, *a, n_vertices=s.n_vertices, n_layers=s.ctx.cfg["layers"],
        dot=check.DOTS[dot]))
    return [np.asarray(r) for r in f(s.params, s.x, *args)]


def check_outputs(s: State, win: dict, limits: dict):
    """Free the program's state, run the reference, compare."""
    got = [[np.asarray(o) for o in out] for out in win.pop("outputs")]
    s.forward = s.runner = s.operands = None      # free the program's state
    ref = reference(s, "highest")
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
    out = reference(s, "3pass")
    s.forward = lambda: out
