"""The program's spans and named scopes read from a profiler trace
(bench/spans.py) and the metrics that read them: on a trace written here in
the protobuf wire format, on the traces recorded on a TPU v5e before the
program had spans or scopes (bench/testdata/), and on a traced serving run
at a tiny size on the CPU."""
import pathlib
import shutil
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench_tiny import ROOT, run_tiny  # noqa: E402

from bench import harness, manifest, spans, xplane  # noqa: E402

DATA = ROOT / "bench" / "testdata"
FULL = ["densify_ms.full", "edge_ms.full", "vertex_ms.full",
        "kernel_glue_ms.full"]
SERVE = ["prep_ms.serve", "bind_ms.serve", "dispatch_ms.serve",
         "fetch_ms.serve", "h2d_mb.serve"]
KERNEL = ('%tile_spmm_pallas.2 = f32[128,354,128]{2,1,0} custom-call(s32[776] '
          '%a), custom_call_target="tpu_custom_call"')


# ---- a trace written in the wire format -------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _int(field, v):
    return _varint(field << 3) + _varint(v)


def _len(field, payload):
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


class _Plane:
    """One ``XPlane``: lines of events, event and stat metadata."""

    def __init__(self, pid, name):
        self.pid, self.name = pid, name
        self.lines, self.events, self.stats = [], {}, {}

    def stat(self, name):
        return self.stats.setdefault(name, len(self.stats) + 1)

    def meta(self, name, tf_op=None, by_ref=False):
        key = (name, tf_op)
        if key not in self.events:
            st = b""
            if tf_op is not None:
                val = (_int(7, self.stat(tf_op)) if by_ref
                       else _len(5, tf_op))
                st = _len(5, _int(1, self.stat("tf_op")) + val)
            self.events[key] = (len(self.events) + 1, st)
        return self.events[key][0]

    def line(self, lid, name, events):
        """``events``: (metadata id, start ns, duration ns, int args)."""
        body = _int(1, lid) + _len(2, name) + _int(3, 0)
        for mid, t, d, args in events:
            ev = _int(1, mid) + _int(2, t * 1000) + _int(3, d * 1000)
            for k, v in args.items():
                ev += _len(4, _int(1, self.stat(k)) + _int(4, v))
            body += _len(4, ev)
        self.lines.append(_len(3, body))

    def encode(self):
        body = _int(1, self.pid) + _len(2, self.name) + b"".join(self.lines)
        for (name, _), (mid, st) in self.events.items():
            em = _int(1, mid) + _len(2, name) + st
            body += _len(4, _int(1, mid) + _len(2, em))
        for name, sid in self.stats.items():
            sm = _int(1, sid) + _len(2, name)
            body += _len(5, _int(1, sid) + _len(2, sm))
        return _len(1, body)


MS = 1_000_000


def _synthetic(path):
    """Two forwards of a program with all four scopes, and one request's
    serving spans, inside a 100 ms window; the device clock runs 1.5 ms
    behind the host's."""
    host = _Plane(1, "/host:CPU")
    span = [("bench.window", 0, 100, {}),
            ("PjitFunction(_run)", 10, 1, {}), ("PjitFunction(_run)", 10, 1, {}),
            ("PjitFunction(_run)", 50, 1, {}),
            ("serve.submit", 5, 90, {"request": 7}),
            ("serve.run_group", 6, 88, {"request": 7}),
            ("serve.bind", 7, 3, {"request": 7, "arrays": 4, "bytes": 3000}),
            ("serve.dispatch", 10, 1, {"request": 7, "arrays": 2,
                                       "bytes": 1000}),
            ("serve.fetch", 11, 80, {"request": 7})]
    host.line(1, "python3", [(host.meta(n), t * MS, d * MS, a)
                             for n, t, d, a in span])
    dev = _Plane(2, "/device:TPU:0")
    ops = [("%fusion.5 = f32[1440256]{0} fusion()",
            "jit(_run)/zipper.densify/jit(densify_edge_weights)/scatter-add",
            2.0, False),
           (KERNEL, "jit(_run)/zipper.kernel/jit(tile_spmm_pallas)/pallas_call",
            3.0, False),
           ("%convert.1 = s32[776]{0} convert()",
            "jit(_run)/zipper.kernel/jit(tile_spmm_pallas)/convert", 1.0,
            False),
           ("%fusion.9 = f32[776,1856]{1,0} fusion()",
            "jit(_run)/zipper.edge/vmap()/gather", 1.0, False),
           ("%fusion.30 = f32[776,360,128]{2,1,0} fusion()",
            "jit(_run)/zipper.vertex/dot_general", 1.0, True),
           ("%sort = (s32[1440256]{0}) sort()", None, 0.5, False)]
    events = []
    for start in (9.0, 48.5):
        t = start
        for name, tf_op, d, ref in ops:
            events.append((dev.meta(name, tf_op, by_ref=ref), int(t * MS),
                           int(d * MS), {}))
            t += d
    # an op of a later program, outside the window
    events.append((dev.meta("%late = f32[1]{0} add()", "jit(_run)/zipper.edge"),
                   150 * MS, MS, {}))
    mod = dev.meta("jit__run(42)")
    dev.line(1, "XLA Modules", [(mod, 9 * MS, 8 * MS, {}),
                                (mod, int(48.5 * MS), 8 * MS, {})])
    dev.line(2, "XLA Ops", events)
    path.write_bytes(host.encode() + dev.encode())
    return path


@pytest.fixture
def synthetic(tmp_path):
    return _synthetic(tmp_path / "synthetic.xplane.pb")


def test_op_paths_read_string_and_reference_stats(synthetic):
    paths = spans.op_paths(synthetic)
    assert set(paths) == {"/device:TPU:0"}
    got = paths["/device:TPU:0"]
    assert got[KERNEL].startswith("jit(_run)/zipper.kernel/")
    assert got["%fusion.30 = f32[776,360,128]{2,1,0} fusion()"] == \
        "jit(_run)/zipper.vertex/dot_general"
    assert got["%sort = (s32[1440256]{0}) sort()"] is None


def test_stage_of_takes_the_first_scope():
    assert spans.stage_of("jit(_run)/zipper.edge/vmap()/gather") == "edge"
    assert spans.stage_of("jit(_run)/zipper.kernel/zipper.edge/x") == "kernel"
    assert spans.stage_of("jit(_run)/vmap()/gather") is None
    assert spans.stage_of(None) is None


def test_summary_of_the_written_trace(synthetic):
    s = spans.summarize(synthetic)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["scoped"] == ["densify", "edge", "kernel", "vertex"]
    # two forwards; the kernel itself is left out, its convert is not
    assert s["stage_s"] == pytest.approx(
        {"densify": 4e-3, "kernel": 2e-3, "edge": 2e-3, "vertex": 2e-3,
         "unscoped": 1e-3})
    assert sum(s["stage_s"].values()) == pytest.approx(
        xplane.reduce(synthetic)["glue_s"])
    assert s["path_share"] == pytest.approx(16 / 17)
    # 9.0 - 10.0 and 48.5 - 50.0 ms: the more negative is the skew
    assert s["skew_s"] == pytest.approx(-1.5e-3) and s["skew_pairs"] == 2
    assert s["spans"]["serve.bind"] == dict(seconds=pytest.approx(3e-3),
                                            count=1, arrays=4, bytes=3000)
    # idle on the host clock: device busy 10.5-19 and 50-58.5 ms
    assert s["idle"] == pytest.approx(
        {"no serve span": 10e-3, "serve.submit": 2e-3,
         "serve.run_group": 4e-3, "serve.bind": 3e-3,
         "serve.dispatch": 0.5e-3, "serve.fetch": 63.5e-3})
    assert sum(s["idle"].values()) == pytest.approx(0.1 - 17e-3)


def _run(n):
    return harness.Run(setup_s=0.0, window={}, work={}, peaks={},
                       trace=None, trace_window={"n": n})


def _reading(monkeypatch, tmp_path, trace, n):
    """Every new reader's value on ``trace``, as the harness would read it
    after a traced stretch of ``n`` iterations."""
    d = tmp_path / "trace"
    d.mkdir(exist_ok=True)
    shutil.copy(trace, d / "t.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", d)
    man = manifest.Manifest()
    return {m: man.reader(m).read(_run(n)) for m in FULL + SERVE}


def test_readers_on_the_written_trace(monkeypatch, tmp_path, synthetic):
    got = _reading(monkeypatch, tmp_path, synthetic, 2)
    assert got == pytest.approx({
        "densify_ms.full": 2.0, "edge_ms.full": 1.0, "vertex_ms.full": 1.0,
        "kernel_glue_ms.full": 1.0, "prep_ms.serve": None,
        "bind_ms.serve": 1.5, "dispatch_ms.serve": 0.5,
        "fetch_ms.serve": 40.0, "h2d_mb.serve": 0.002})


@pytest.mark.parametrize("trace", ["gcn2-uniform45k-full.3fwd.xplane.pb",
                                   "gcn2-molhiv.3req.xplane.pb"])
def test_readers_find_nothing_in_a_program_without_spans(monkeypatch,
                                                         tmp_path, trace):
    """The traces were recorded before the program had scopes or spans: a
    program without them reports none of these metrics, and no error."""
    got = _reading(monkeypatch, tmp_path, DATA / trace, 3)
    assert got == {m: None for m in FULL + SERVE}


def test_recorded_trace_has_paths_for_nearly_all_device_time():
    s = spans.summarize(DATA / "gcn2-uniform45k-full.3fwd.xplane.pb")
    assert s["path_share"] >= 0.99
    assert s["scoped"] == [] and s["stage_s"] == pytest.approx(
        {"unscoped": xplane.reduce(
            DATA / "gcn2-uniform45k-full.3fwd.xplane.pb")["glue_s"]})
    # each forward's module starts about 1 ms before its host call
    assert s["skew_pairs"] == 3 and -1.2e-3 < s["skew_s"] < -0.8e-3


def test_serving_trace_module_launches_pair_with_host_calls():
    s = spans.summarize(DATA / "gcn2-molhiv.3req.xplane.pb")
    assert s["skew_pairs"] == 18 and s["skew_s"] == 0.0
    assert s["spans"] == {} and s["idle"] == {}


def test_innermost_pieces_of_nested_spans():
    got = spans.innermost([(0, 10, "a"), (2, 4, "b"), (4, 7, "c"),
                           (5, 6, "d")])
    assert got == [(0, 2, "a"), (2, 4, "b"), (4, 5, "c"), (5, 6, "d"),
                   (6, 7, "c"), (7, 10, "a")]


def test_gaps_split_over_named_pieces():
    pieces = [(0, 2, "a"), (2, 4, "b"), (6, 8, "c")]
    got = spans.overlap_by_name([(1, 3), (3.5, 7), (9, 10)], pieces)
    assert got == pytest.approx({"a": 1, "b": 1.5, "no serve span": 3.0,
                                 "c": 1})


def test_traced_serving_run_reads_the_stage_spans(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    r, _ = run_tiny("gcn2-molhiv", trace=True)
    assert r["correct"] is True
    m = r["metrics"]
    assert all(m[k]["value"] > 0 for k in SERVE)
    # no TPU plane on the CPU: no device metric
    assert not any(k in m for k in FULL)
