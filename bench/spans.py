"""Read the program's own spans and named scopes from a profiler trace
(``.xplane.pb``): what the per-layer metrics of the scheduled program's
stages and of the serving engine's stages read.

Inside the host span that marks the traced window (``bench.window``):

* ``stage_s``: device seconds of the operations that are not Mosaic kernels,
  by the stage of the scheduled program that emitted them: the first
  ``zipper.<stage>`` component of the op's JAX path (the ``tf_op`` stat of
  the op's event metadata); ``unscoped`` where the path has none or there is
  no path.  Op times are clipped to the window as :func:`xplane.reduce`
  clips them, so the stages and ``unscoped`` add up to its ``glue_s``;
* ``spans``: per ``serve.*`` span name, the host seconds, the count, and
  the sums of the ``arrays`` and ``bytes`` arguments;
* ``skew_s``: the device clock's offset from the host's: for each
  ``jit__run`` module on a device's ``XLA Modules`` line, its start minus
  the start of the host call (``PjitFunction(_run)``) that launched it,
  paired in order; the most negative value, else 0;
* ``idle``: device idle seconds by the innermost ``serve.*`` span open on
  the benchmark's thread, with the device's times moved onto the host clock
  by ``skew_s`` first.

``jax.profiler.ProfileData`` gives events, host span arguments and times;
it does not give an event's metadata stats, so :func:`op_paths` walks the
device planes' event metadata in the protobuf wire format (no TensorFlow),
skipping the planes' lines unread.  A trace is read once per file.
"""
from __future__ import annotations

import functools
import pathlib
from typing import Dict, Iterator, List, Optional, Tuple

from bench import xplane

STAGES = ("vertex", "edge", "densify", "kernel")
SCOPE = "zipper."
SERVE = "serve."
HOST_CALL = "PjitFunction(_run)"
MODULE = "jit__run"
MODULES_LINE = "XLA Modules"
TF_OP = "tf_op"


# ---- protobuf wire format -------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    ``(start, end)`` pair for a length-delimited field, raw bytes for a
    fixed-width one."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            yield field, bytes(buf[i:i + n])
            i += n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The value (field 2) of one protobuf map entry."""
    return next((v for f, v in _fields(buf, *span) if f == 2), None)


def op_paths(path) -> Dict[str, Dict[str, Optional[str]]]:
    """Per device plane, each event name's ``tf_op`` path (``None`` where the
    metadata has none, or two entries of one name disagree).

    ``XSpace.planes`` = 1; ``XPlane``: name 2, lines 3 (skipped),
    event_metadata 4, stat_metadata 5; ``XEventMetadata``: name 2, stats 5;
    ``XStat``: metadata_id 1, str_value 5, ref_value 7; ``XStatMetadata``:
    id 1, name 2."""
    buf = memoryview(pathlib.Path(path).read_bytes())
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, pv)
            elif pf == 4:
                events.append(pv)
            elif pf == 5:
                sm = _map_value(buf, pv)
                if sm is not None:
                    sid, sname = 0, ""
                    for sf, sv in _fields(buf, *sm):
                        if sf == 1:
                            sid = sv
                        elif sf == 2:
                            sname = _text(buf, sv)
                    stat_names[sid] = sname
        if not xplane.DEVICE_PLANE.match(name):
            continue
        tf_ids = {k for k, v in stat_names.items() if v == TF_OP}
        paths: Dict[str, Optional[str]] = {}
        for entry in events:
            em = _map_value(buf, entry)
            if em is None:
                continue
            ename, op = "", None
            for ef, ev in _fields(buf, *em):
                if ef == 2:
                    ename = _text(buf, ev)
                elif ef == 5:
                    sid, val = None, None
                    for sf, sv in _fields(buf, *ev):
                        if sf == 1:
                            sid = sv
                        elif sf == 5:
                            val = _text(buf, sv)
                        elif sf == 7:
                            val = stat_names.get(sv)
                    if sid in tf_ids:
                        op = val
            if ename in paths and paths[ename] != op:
                op = None
            paths[ename] = op
        out[name] = paths
    return out


def stage_of(op_path: Optional[str]) -> Optional[str]:
    """The first ``zipper.<stage>`` component of an op's JAX path."""
    for part in (op_path or "").split("/"):
        if part.startswith(SCOPE):
            return part[len(SCOPE):]
    return None


# ---- intervals --------------------------------------------------------------

def innermost(spans: List[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """Split one thread's nested spans into disjoint pieces, each named by
    the innermost span open over it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []
    t = 0.0
    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            end, n = stack.pop()
            out.append((t, end, n))
            t = end
        if stack:
            out.append((t, a, stack[-1][1]))
        stack.append((b, name))
        t = a
    while stack:
        end, n = stack.pop()
        out.append((t, end, n))
        t = end
    return [(a, b, n) for a, b, n in out if b > a]


def overlap_by_name(gaps: List[Tuple[float, float]],
                    pieces: List[Tuple[float, float, str]],
                    rest: str = "no serve span") -> Dict[str, float]:
    """Length of the sorted, disjoint ``gaps`` inside each named piece
    (sorted, disjoint); what no piece covers goes under ``rest``."""
    got: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi = max(a, pieces[k][0]), min(b, pieces[k][1])
            if hi > lo:
                got[pieces[k][2]] = got.get(pieces[k][2], 0.0) + hi - lo
                covered += hi - lo
            k += 1
        if b - a > covered:
            got[rest] = got.get(rest, 0.0) + (b - a) - covered
    return got


def _outermost(calls: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(calls):
        if out and a < out[-1][1]:
            continue
        out.append((a, b))
    return out


def clock_skew_ns(host_calls: List[Tuple[float, float]],
                  modules: List[float]) -> Tuple[float, int]:
    """(skew, pairs): the most negative of module start minus host call
    start, paired in order, else 0; no pairs where the counts differ."""
    calls = _outermost(host_calls)
    modules = sorted(modules)
    if not calls or len(calls) != len(modules):
        return 0.0, 0
    return min(0.0, min(m - c[0] for m, c in zip(modules, calls))), len(calls)


# ---- one trace ----------------------------------------------------------------

def summarize(path, window_span: str = "bench.window") -> Optional[Dict]:
    """What the span and scope metrics read in one trace; ``None`` where the
    trace has no window span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    threads: List[List[Tuple[float, float, str, Dict]]] = []
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    modules: Dict[str, List[float]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                threads.append([
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                     dict(ev.stats) if ev.name.startswith(SERVE) else {})
                    for ev in line.events])
        elif xplane.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == xplane.OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        ev.start_ns for ev in line.events
                        if ev.name.startswith(MODULE))
    own = next((t for t in threads
                if any(n == window_span for _, _, n, _ in t)), None)
    if own is None:
        return None
    w0, w1 = next((a, b) for a, b, n, _ in own if n == window_span)

    # host: serve.* spans inside the window, on every thread
    spans: Dict[str, Dict[str, float]] = {}
    for t in threads:
        for a, b, n, st in t:
            if not n.startswith(SERVE) or a < w0 or b > w1:
                continue
            s = spans.setdefault(n, dict(seconds=0.0, count=0, arrays=0,
                                         bytes=0))
            s["seconds"] += (b - a) * 1e-9
            s["count"] += 1
            s["arrays"] += int(st.get("arrays", 0))
            s["bytes"] += int(st.get("bytes", 0))
    calls = [(a, b) for t in threads for a, b, n, _ in t if n == HOST_CALL]
    skews = [clock_skew_ns(calls, m) for m in modules.values()]
    skew, pairs = min(skews, default=(0.0, 0))

    # device: non-kernel op time by stage, and idle gaps on the host clock
    paths = op_paths(path) if devices else {}
    stage_s: Dict[str, float] = {}
    scoped = set()
    found = total = 0.0
    gaps: List[Tuple[float, float]] = []
    busy = []
    for dev, evs in devices.items():
        known = paths.get(dev, {})
        ivs = []
        for a, b, name in evs:
            op = known.get(name)
            stage = stage_of(op)
            if stage is not None:
                scoped.add(stage)
            if min(b - skew, w1) > max(a - skew, w0):
                ivs.append((max(a - skew, w0), min(b - skew, w1)))
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            total += b - a
            found += (b - a) if op else 0.0
            if not xplane.is_kernel(name):
                key = stage if stage in STAGES else "unscoped"
                stage_s[key] = stage_s.get(key, 0.0) + (b - a) * 1e-9
        if not ivs:
            continue
        length, merged = xplane.union_length(ivs)
        busy.append(length)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    n_dev = max(len(busy), 1)
    pieces = innermost([(a, b, n) for a, b, n, _ in own
                        if n.startswith(SERVE)])
    idle = overlap_by_name(sorted(gaps), pieces) if pieces else {}
    return dict(
        window_s=(w1 - w0) * 1e-9,
        stage_s={k: v / n_dev for k, v in stage_s.items()},
        scoped=sorted(scoped),
        path_share=(found / total) if total else None,
        spans=spans, skew_s=skew * 1e-9, skew_pairs=pairs,
        idle={k: v * 1e-9 / n_dev for k, v in
              sorted(idle.items(), key=lambda kv: -kv[1])})


def _line(s: Dict) -> str:
    idle = ", ".join(f"{k} {v:.4f}" for k, v in s["idle"].items()) or "none"
    stages = ", ".join(f"{k} {v:.4f}" for k, v in
                       sorted(s["stage_s"].items())) or "none"
    share = ("none" if s["path_share"] is None
             else f"{100 * s['path_share']:.2f} %")
    return (f"[spans] clock skew {1e3 * s['skew_s']:.4f} ms from "
            f"{s['skew_pairs']} launches; device idle s by serve stage: "
            f"{idle}; non-kernel device s by stage: {stages}; device time "
            f"with a JAX path {share}")


@functools.lru_cache(maxsize=4)
def _summary_of(path: str, mtime_ns: int, size: int) -> Optional[Dict]:
    s = summarize(path)
    if s is not None:
        print(_line(s), flush=True)
    return s


def summary(run) -> Optional[Dict]:
    """The summary of a traced run's trace (read once per file), or
    ``None`` where the run was not traced or left no trace."""
    from bench import harness

    if not run.trace_window:
        return None
    try:
        p = xplane.newest_trace(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    st = p.stat()
    return _summary_of(str(p), st.st_mtime_ns, st.st_size)


def device_ms(run, stage: str) -> Optional[float]:
    """Device ms per forward of the non-kernel ops under ``zipper.<stage>``,
    or ``None`` where no op of the trace carries that scope."""
    s = summary(run)
    n = run.trace_window["n"] if run.trace_window else 0
    if s is None or not n or stage not in s["scoped"]:
        return None
    return 1e3 * s["stage_s"].get(stage, 0.0) / n


def _per_request(run, names, field: str) -> Optional[float]:
    s = summary(run)
    n = run.trace_window["n"] if run.trace_window else 0
    got = [s["spans"][k][field] for k in names if s and k in s["spans"]]
    return sum(got) / n if n and got else None


def host_ms(run, names) -> Optional[float]:
    """Host ms per completed request in the ``serve.*`` spans ``names``, or
    ``None`` where the trace has none of them."""
    v = _per_request(run, names, "seconds")
    return None if v is None else 1e3 * v


def host_mb(run, names) -> Optional[float]:
    """MB per completed request in the ``bytes`` arguments of the
    ``serve.*`` spans ``names``, or ``None`` where the trace has none."""
    v = _per_request(run, names, "bytes")
    return None if v is None else v / 1e6
