"""The knowledge-graph cell (``rgcn2-fb237-full``) and the CSR twin of the
geometric gcn cell (``gcn2-rgg45k-csr-full``) at a tiny size on the CPU:
set-up and window, the control and both faults failing the comparison,
the graph generator, the work counts and the relation kernel's readers."""
import pathlib
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench_tiny import run_tiny  # noqa: E402

from bench import gen_kg, harness, manifest  # noqa: E402
from bench.manifest import BenchError  # noqa: E402

KG = "rgcn2-fb237-full"
CSR = "gcn2-rgg45k-csr-full"
MAN = manifest.Manifest()
TINY_KG = {
    "traffic": {"entities": 300, "relations": 6, "triples": 1200,
                "warmup_forwards": 1},
    "config": {"relations": 12, "blocks": 4, "in_dim": 20,
               "hidden_dim": 20, "out_dim": 20,
               "params": {"l0.W_rel": [12, 4, 5, 5], "l0.W_self": [20, 20],
                          "l1.W_rel": [12, 4, 5, 5],
                          "l1.W_self": [20, 20]}},
}


def run_kg(trace=False, patch=None, overrides=TINY_KG):
    lines = []
    result = harness.run_cell(KG, 2**31 + 17, 0.3, trace,
                              t_start=time.perf_counter(),
                              require_chip=False, overrides=overrides,
                              patch=patch, log=lines.append)
    return result, lines


def test_kg_cell_sets_up_and_runs():
    r, lines = run_kg()
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    want = {m["name"] for m in MAN.metrics_of(KG, False)}
    assert set(r["metrics"]) == want == {"forward_ms", "setup_s"}
    assert any(ln.startswith("[graph] 300 entities") for ln in lines)


def test_kg_work_reads_the_layout_counter():
    """The loop's work holds the relation layout's rows, which the
    ``rel_pad_share.full`` reader turns into a share."""
    seen = {}
    r, lines = run_kg(patch=lambda loop, s: seen.update(loop.work(s)))
    assert r["correct"] is True
    assert seen["rel_real_rows"] == 2 * 1200
    assert seen["rel_groups"] == 12
    assert any("relation layout {'real_rows': 2400" in ln for ln in lines)
    run = harness.Run(setup_s=1.0, window={}, work=seen, peaks={})
    pad = MAN.reader("rel_pad_share.full").read(run)
    assert pad == pytest.approx(100.0 * seen["rel_padded_rows"] / 2400)


@pytest.mark.parametrize("cell", [KG, CSR])
def test_control_is_not_correct(cell):
    run = run_kg if cell == KG else (lambda patch: run_tiny(cell, patch=patch))
    r, _ = run(patch=lambda loop, s: loop.control(s))
    assert r["correct"] is False
    c = r["checks"]["rel_err"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer"])
@pytest.mark.parametrize("cell", [KG, CSR])
def test_fault_is_not_correct(cell, fault):
    run = run_kg if cell == KG else (lambda patch: run_tiny(cell, patch=patch))
    r, _ = run(patch=lambda loop, s: loop.fault(s, fault))
    assert r["correct"] is False
    assert r["checks"]["rel_err"]["value"] > r["checks"]["rel_err"]["limit"]


def test_csr_cell_sets_up_and_runs():
    r, lines = run_tiny(CSR)
    assert r["correct"] is True
    assert any("layout=csr" in ln for ln in lines)


def test_program_without_typed_layers_stops_at_build(monkeypatch):
    """A program whose ``trace_stacked`` takes no relation count or blocks
    (the tree before this cell) stops at the first set-up step, before the
    graph is generated or tiled."""
    from repro.gnn import models

    def untyped(name, n_layers, in_dim=128, hidden_dim=128, out_dim=128):
        raise AssertionError("not reached")

    monkeypatch.setattr(models, "trace_stacked", untyped)
    lines = []
    with pytest.raises(BenchError, match="cannot build"):
        harness.run_cell(KG, 1, 0.3, False, t_start=time.perf_counter(),
                         require_chip=False, log=lines.append)
    assert not any(ln.startswith(("[setup] graph", "[graph]",
                                  "[setup] tiling")) for ln in lines)


def test_kg_graph_has_the_dataset_shape():
    p = dict(MAN.traffic("fb237-full"), entities=2000, triples=30000)
    src, dst, et = gen_kg.kg_graph(p, log=lambda s: None)
    R, n = p["relations"], p["triples"]
    assert len(src) == 2 * n
    assert not np.any(src == dst)
    # forward triples distinct, each stored inverse as r + R
    fwd = (et[:n].astype(np.int64) * 2000 + src[:n]) * 2000 + dst[:n]
    assert len(np.unique(fwd)) == n
    np.testing.assert_array_equal(src[n:], dst[:n])
    np.testing.assert_array_equal(et[n:], et[:n] + R)
    assert set(np.unique(et[:n])) == set(range(R))
    again = gen_kg.kg_graph(p, log=lambda s: None)
    assert all(np.array_equal(a, b) for a, b in zip((src, dst, et), again))


def test_relation_sizes_sum_and_skew():
    s = gen_kg.relation_sizes(272115, 237, 0.8)
    assert s.sum() == 272115 and s.min() >= 1
    assert np.all(np.diff(s) <= 0) and s[0] > 50 * s[-1]


def test_rgcn_counts_at_full_cell_size():
    """Per layer: E (2 F k + 2 F) FLOPs, and (2 V F + 3 E + R F k) f32."""
    c = MAN.config("rgcn2-fb237-bdd500")
    m = MAN.model("rgcn")
    V, E = 14541, 544230
    kf, kb = m.kernel_work(c, V, E)
    assert kf == 2 * E * (2 * 500 * 5 + 2 * 500)
    assert kb == 2 * (2 * V * 500 + 3 * E + 474 * 500 * 5) * 4
    assert abs(kb / 2 - 69.43e6) < 0.01e6
    # self transform + typed messages and sums + add, one ReLU
    assert m.model_flops(c, V, E) == (2 * (2 * V * 500 * 500 + kf / 2
                                           + V * 500) + V * 500)


def test_relation_kernel_readers():
    ms = MAN.reader("rel_kernel_ms.full")
    roof = MAN.reader("rel_kernel_roofline.full")
    pad = MAN.reader("rel_pad_share.full")
    run = harness.Run(
        setup_s=1.0, window={}, peaks={"flops_per_s": 1e12,
                                       "hbm_bytes_per_s": 1e9},
        work={"rel_kernel_flops": 1e9, "rel_kernel_bytes": 1e6,
              "rel_real_rows": 1000, "rel_padded_rows": 50},
        trace={"ops": [("%relation_transform.1 = tpu_custom_call "
                        "f32[8,640]", 0.004),
                       ("%relation_sum.2 = tpu_custom_call "
                        "f32[8,640]", 0.002),
                       ("%fusion.7 = fusion f32[8,640]", 0.5)]},
        trace_window={"n": 2})
    assert ms.read(run) == pytest.approx(3.0)
    # least time max(1 ms, 1 ms) per forward over 3 ms
    assert roof.read(run) == pytest.approx(100.0 / 3)
    assert pad.read(run) == pytest.approx(5.0)
    run.trace = {"ops": [("%fusion.7 = fusion f32[8,640]", 0.5)]}
    assert ms.read(run) is None and roof.read(run) is None
