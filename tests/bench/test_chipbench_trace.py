"""The reduction from a profiler trace to the benchmark's device numbers
(bench/xplane.py), on small traces recorded on a TPU v5e and kept under
bench/testdata/."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import xplane  # noqa: E402

DATA = ROOT / "bench" / "testdata"


def test_union_length_merges_overlaps():
    total, merged = xplane.union_length([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert total == 6 and merged == [(0, 3), (5, 8)]


def test_host_activity_is_the_innermost_open_span():
    spans = [(0, 100, "bench.window"), (10, 50, "bench.request"),
             (20, 30, "DevicePutWithSharding"), (60, 70, "bench.request")]
    got = xplane._host_activity(spans, [5, 15, 25, 40, 65, 90],
                                "bench.window")
    assert got == ["no host span", "bench.request", "DevicePutWithSharding",
                   "bench.request", "bench.request", "no host span"]


def test_kernels_are_told_by_their_hlo_not_their_name():
    kern = ('%renamed.2 = f32[32,1416,128]{2,1,0} custom-call(s32[1024]{0} '
            '%a), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={s32[1024]{0}}')
    glue = ('%tile_spmm_pallas_like.1 = f32[1416,136,1024]{1,0,2} '
            'copy(f32[1416,136,1024]{2,1,0} %reshape.141)')
    assert xplane.is_kernel(kern) and not xplane.is_kernel(glue)
    assert xplane.short_name(kern) == \
        "%renamed.2 = tpu_custom_call f32[32,1416,128]"
    assert xplane.short_name(glue) == \
        "%tile_spmm_pallas_like.1 = copy f32[1416,136,1024]"


def _ops_in_window(path):
    """The traced window's device ops, read straight from the file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host = [ev for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for ev in line.events
            if ev.name == "bench.window"]
    w0 = host[0].start_ns
    w1 = w0 + host[0].duration_ns
    ops = [ev for p in pd.planes if p.name == "/device:TPU:0"
           for line in p.lines if line.name == "XLA Ops"
           for ev in line.events
           if ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0]
    return (w0, w1), ops


def test_full_graph_trace_three_forwards():
    """gcn on a 45,293-vertex uniform graph (32x32 tiles), three forwards
    traced on a v5e."""
    path = DATA / "gcn2-uniform45k-full.3fwd.xplane.pb"
    r = xplane.reduce(path)
    (w0, w1), ops = _ops_in_window(path)
    kernels = [ev for ev in ops if xplane.is_kernel(ev.name)]
    # two layers, one weighted-SpMM kernel each, per forward
    assert len(kernels) == 6
    assert all("tile_spmm_pallas" in ev.name for ev in kernels)
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert r["window_s"] == pytest.approx(0.062901938)
    assert r["busy_s"] == pytest.approx(0.058924959)
    assert r["kernel_s"] == pytest.approx(0.018380778)
    assert r["glue_s"] == pytest.approx(0.040544181)
    assert r["devices"] == 1
    # the sum of op times covers the union at least; idle is the rest
    assert r["kernel_s"] + r["glue_s"] >= r["busy_s"] - 1e-9
    assert sum(v for _, v in r["idle"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert r["ops"][0][0] == "%copy.16 = copy f32[1416,136,1024]"
    assert r["idle"][0][0] == "bench.forward"


def test_serving_trace_three_requests():
    """gcn2-molhiv, three requests traced on a v5e (PR 12): the device is
    idle nearly all the time, mostly while the host puts request arrays on
    it."""
    r = xplane.reduce(DATA / "gcn2-molhiv.3req.xplane.pb")
    assert r["window_s"] == pytest.approx(0.091015557)
    assert r["busy_s"] == pytest.approx(0.001215066)
    assert r["kernel_s"] == pytest.approx(8.2762e-05)
    assert 1 - r["busy_s"] / r["window_s"] > 0.98
    assert r["idle"][0][0] == "DevicePutWithSharding"
    assert sum(v for _, v in r["idle"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_trace_without_the_window_span_reads_nothing(tmp_path):
    assert xplane.reduce(DATA / "gcn2-molhiv.3req.xplane.pb",
                         window_span="no.such.span") is None
