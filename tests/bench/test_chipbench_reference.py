"""The benchmark's plain references (bench/configs/<model>.py) against the
program's whole-graph reference, and the control's precision."""
import pathlib
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, gen  # noqa: E402
from bench.manifest import Manifest  # noqa: E402

MAN = Manifest()


def setup_case(name, V=300, E=450, seed=4):
    from repro.gnn import graphs, models
    cfg = MAN.config(name)
    mod = MAN.model(cfg["model"])
    src, dst = gen.geometric_graph(V, E, seed)
    g = graphs.Graph(src=src, dst=dst, n_vertices=V)
    tr = models.trace_stacked(cfg["model"], cfg["layers"])
    params = models.init_params(tr, seed=seed)
    x = np.random.default_rng(seed).standard_normal(
        (V, cfg["in_dim"])).astype(np.float32)
    return cfg, mod, g, tr, params, x


def run_bench_ref(cfg, mod, g, params, x, dot):
    return mod.forward(params, x, g.src, g.dst, n_vertices=g.n_vertices,
                       n_layers=cfg["layers"], dot=check.DOTS[dot])


@pytest.mark.parametrize("name", ["gcn2-e128", "gat2-e128"])
def test_reference_matches_program_reference(name):
    from repro.core import executor
    cfg, mod, g, tr, params, x = setup_case(name)
    inputs = mod.vertex_inputs(x, g.src, g.dst, g.n_vertices)
    want = executor.run_reference(tr, g, inputs, params)
    got = run_bench_ref(cfg, mod, g, params, x, "highest")
    assert len(got) == len(want)
    err = check.MaxRelErr()
    for a, b in zip(got, want):
        err.add(a, b)
    assert err.bad == 0 and err.value() < 1e-6


@pytest.mark.parametrize("name", ["gcn2-e128", "gat2-e128"])
def test_three_pass_control_reads_far_above_rounding(name):
    cfg, mod, g, tr, params, x = setup_case(name)
    hi = run_bench_ref(cfg, mod, g, params, x, "highest")
    lo = run_bench_ref(cfg, mod, g, params, x, "3pass")
    err = check.MaxRelErr()
    for a, b in zip(lo, hi):
        err.add(a, b)
    assert 1e-6 < err.value() < 1e-3


def test_three_pass_dot_is_three_bf16_passes():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 128))
    b = jax.random.normal(jax.random.PRNGKey(1), (128, 32))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    e3 = np.abs(np.asarray(check.dot_3pass(a, b)) - exact).max()
    e6 = np.abs(np.asarray(check.dot_highest(a, b)) - exact).max()
    one = np.abs(np.asarray(
        jax.numpy.matmul(a.astype("bfloat16"), b.astype("bfloat16"),
                         preferred_element_type="float32")) - exact).max()
    assert e6 < e3 < one / 30
