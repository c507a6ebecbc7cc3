"""Named scopes on the scheduled program's stages (``core/pipeline.py``).

``PipelinedRunner._run`` opens every op it emits under one of four scopes,
``zipper.vertex``, ``zipper.edge``, ``zipper.densify`` and
``zipper.kernel``, so a device trace can split the program's non-kernel
time by stage.  A scope only tags the ops' ``op_name`` metadata: the
program compiled with the scopes must be the program compiled without them.
"""
import contextlib
import re

import numpy as np
import pytest

from repro.core import compiler, pipeline, tiling
from repro.gnn import graphs, models

STAGES = {"zipper.vertex", "zipper.edge", "zipper.densify", "zipper.kernel"}
CASES = [("gcn", "coo", True), ("gat", "coo", True), ("rgcn", "coo", True),
         ("gcn", "csr", True), ("gat", "csr", True), ("gcn", "coo", False)]


def _runner(model, layout, kernel_dispatch):
    g = graphs.random_graph(96, 400, seed=3, model="powerlaw",
                            n_edge_types=3 if model == "rgcn" else None)
    tiles, _ = tiling.build_tiles(g, 3, 3, layout=layout)
    trace = models.trace_stacked(model, 2, 16, 16, 16)
    r = pipeline.PipelinedRunner(compiler.compile_gnn(trace), g, tiles,
                                 kernel_dispatch=kernel_dispatch)
    rng = np.random.default_rng(0)
    params = {k: rng.standard_normal(v).astype(np.float32)
              for k, v in trace.params.items()}
    inputs = {}
    for _, name in r.sp.vertex_inputs:
        inputs[name] = rng.standard_normal(
            (g.n_vertices, 16 if name == "x" else 1)).astype(np.float32)
    for _, name in r.sp.edge_inputs:
        inputs[name] = rng.standard_normal((g.n_edges, 1)).astype(np.float32)
    return r, inputs, params


def _compiled_text(model, layout, kernel_dispatch):
    r, inputs, params = _runner(model, layout, kernel_dispatch)
    return r.lower(inputs, params).compile().as_text()


def _without_metadata(hlo: str) -> str:
    """HLO text without op metadata and the stack-frame tables it points
    into."""
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    return "\n".join(
        ln for ln in hlo.splitlines()
        if not re.match(r"^(\d+ |FileNames$|FunctionNames$|FileLocations$"
                        r"|StackFrames$)", ln))


def _scopes(hlo: str):
    return {part for path in re.findall(r'op_name="([^"]*)"', hlo)
            for part in path.split("/") if part.startswith("zipper.")}


@pytest.mark.parametrize("model,layout,kernel_dispatch", CASES)
def test_stage_scopes_tag_the_program(model, layout, kernel_dispatch):
    got = _scopes(_compiled_text(model, layout, kernel_dispatch))
    if model == "rgcn":                 # the relation path has no densify
        assert got == STAGES - {"zipper.densify"}
    elif kernel_dispatch and layout == "coo":
        assert got == STAGES
    elif kernel_dispatch:               # CSR kernels walk rows: no densify
        assert got == STAGES - {"zipper.densify"}
    else:                               # the scan schedule: no kernel
        assert got == {"zipper.vertex", "zipper.edge"}


@pytest.mark.parametrize("model,layout,kernel_dispatch", CASES[:3])
def test_stage_scopes_change_no_op(model, layout, kernel_dispatch,
                                   monkeypatch):
    scoped = _compiled_text(model, layout, kernel_dispatch)
    monkeypatch.setattr(pipeline, "_stage",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_text(model, layout, kernel_dispatch)
    assert not _scopes(plain) and _scopes(scoped)
    assert _without_metadata(scoped) == _without_metadata(plain)
