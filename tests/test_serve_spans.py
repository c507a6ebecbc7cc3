"""Profiler spans of the serving engine (``serve/engine.py``).

``InferenceServer.submit`` opens ``serve.submit`` and, per size class,
``serve.run_group`` with its stages nested inside; every span carries the
request's id, and ``serve.bind`` / ``serve.dispatch`` count the arrays and
bytes they hand to the device (not the edge counts ``bind`` builds there).  The tree is read back from a real
``jax.profiler`` trace on the CPU.
"""
import jax
import pytest
from jax.profiler import ProfileData

from repro.core import compiler, pipeline
from repro.gnn import graphs, models
from repro.serve import InferenceServer, size_class

STAGES = ["serve.merge", "serve.canonical", "serve.inputs", "serve.lookup",
          "serve.bind", "serve.dispatch", "serve.fetch"]


def _request(tr, sizes, seed):
    gs = [graphs.random_graph(v, 4 * v, seed=seed + k, model="powerlaw")
          for k, v in enumerate(sizes)]
    return gs, [models.init_inputs(tr, g, seed=seed + k)
                for k, g in enumerate(gs)]


def _spans(trace_dir):
    """serve.* spans of the trace: (start, end, name, args)."""
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                         dict(ev.stats)) for ev in line.events
                        if ev.name.startswith("serve.")]
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two requests of graphs in two size classes, traced; the operands
    each ``bind`` returned, in call order."""
    tr = models.trace_named("gcn", 16, 16)
    server = InferenceServer(compiler.compile_gnn(tr),
                             models.init_params(tr, seed=0))
    requests = [_request(tr, [20, 24, 200], seed=0),
                _request(tr, [21, 23, 205], seed=10)]
    bound = []
    real_bind = pipeline.PipelinedRunner.bind

    def bind(self, tiles, reordering=None):
        ops = real_bind(self, tiles, reordering)
        bound.append(ops)
        return ops

    trace_dir = tmp_path_factory.mktemp("trace")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pipeline.PipelinedRunner, "bind", bind)
        with jax.profiler.trace(str(trace_dir)):
            for gs, ins in requests:
                server.submit(gs, ins)
    return requests, _spans(trace_dir), bound


def test_one_submit_span_per_request_with_its_own_id(traced):
    requests, spans, _ = traced
    submits = [s for s in spans if s[2] == "serve.submit"]
    assert len(submits) == len(requests)
    ids = [s[3]["request"] for s in submits]
    assert len(set(ids)) == len(ids)
    for s, (gs, _) in zip(submits, requests):
        assert s[3]["graphs"] == len(gs)
        assert s[3]["groups"] == len({size_class(g) for g in gs})
        mine = [x for x in spans if x[3].get("request") == s[3]["request"]]
        # every span of the request carries its id and nests in its submit
        assert all(_inside(x, s) for x in mine)
        assert len([x for x in spans if _inside(x, s)]) == len(mine)


def test_run_group_per_size_class_with_the_stages_in_order(traced):
    requests, spans, _ = traced
    names = {s[2] for s in spans}
    assert names == {"serve.submit", "serve.group", "serve.run_group",
                     *STAGES}
    for s in (x for x in spans if x[2] == "serve.submit"):
        rid = s[3]["request"]
        mine = [x for x in spans if x[3]["request"] == rid]
        groups = [x for x in mine if x[2] == "serve.run_group"]
        assert len(groups) == s[3]["groups"]
        assert sum(g[3]["graphs"] for g in groups) == s[3]["graphs"]
        assert [x[2] for x in mine if x[2] == "serve.group"] == ["serve.group"]
        for g in groups:
            inner = [x for x in mine if _inside(x, g) and x is not g]
            assert [x[2] for x in inner] == STAGES
            # the stages follow one another on the one thread
            assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))


def test_bind_counts_the_bound_operands(traced):
    _, spans, bound = traced
    binds = [s for s in spans if s[2] == "serve.bind"]
    assert len(binds) == len(bound)
    for s, ops in zip(binds, bound):
        leaves = pipeline.host_leaves(ops)
        assert s[3]["arrays"] == len(leaves)
        assert s[3]["bytes"] == sum(a.nbytes for a in leaves) > 0
        # the edge count is scattered on the device at bind: no copy
        counts = [kc["cnt"] for kc in ops[1]]
        assert len(jax.tree_util.tree_leaves(ops)) == len(leaves) + len(counts)
        assert counts and all(c.ndim == 3 for c in counts)


def test_dispatch_counts_host_arrays_and_lookup_reports_hits(traced):
    _, spans, _ = traced
    for s in (x for x in spans if x[2] == "serve.dispatch"):
        # host inputs (x, dnorm) and the numpy params are handed over
        assert s[3]["arrays"] >= 2 and s[3]["bytes"] > 0
    hits = [s[3]["hit"] for s in spans if s[2] == "serve.lookup"]
    # first request builds both classes; the second meets them again
    assert hits == [0, 0, 1, 1]
