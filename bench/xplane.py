"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

Within the host span that marks the traced window (``bench.window``):

* ``busy_s``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged
  over the devices that ran any; ``window_s``: the span's length;
* ``kernel_s`` / ``glue_s``: summed device time of the operations that are
  Mosaic kernels (their HLO instruction, which names each event, is a
  custom call to ``tpu_custom_call``; never the kernel function's name) and
  of all the others;
* ``ops``: device seconds per operation (``%name = opcode shape``), largest
  first;
* ``idle``: device idle seconds inside the window summed by what the host
  was doing then: the innermost host span open at the middle of each gap.
"""
from __future__ import annotations

import pathlib
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
Interval = Tuple[float, float]


def newest_trace(trace_dir) -> pathlib.Path:
    """The most recent ``.xplane.pb`` the profiler wrote under a dir."""
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def is_kernel(hlo_text: str) -> bool:
    """True where the op's HLO instruction, which a TPU trace gives as the
    event's name, is a custom call to ``tpu_custom_call``."""
    return KERNEL_MARK in hlo_text


_HLO = re.compile(r"^(%[\w.\-]+) = ([a-z0-9]+\[[^\]]*\])\S* ([\w\-]+)\(")


def short_name(hlo_text: str) -> str:
    """``%name = opcode shape`` of an HLO instruction, without its operands
    and layouts (the instruction's own name where the shape is a tuple)."""
    m = _HLO.match(hlo_text)
    if m:
        name, shape, op = m.groups()
        if KERNEL_MARK in hlo_text:
            op = "tpu_custom_call"
        return f"{name} = {op} {shape}"
    return hlo_text.split(" = ", 1)[0][:120]


def union_length(ivs: List[Interval]) -> Tuple[float, List[Interval]]:
    """Total length of the union of intervals, and the merged intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(ivs):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def _host_activity(spans, times: List[float], skip: str) -> List[str]:
    """Innermost span of one host thread open at each of the sorted
    ``times``; a thread's spans nest, so a stack sweep finds it."""
    spans = sorted((a, -b, n) for a, b, n in spans if n != skip)
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            a, nb, n = spans[i]
            while stack and stack[-1][0] <= a:
                stack.pop()
            stack.append((-nb, n))
            i += 1
        while stack and stack[-1][0] <= t:
            stack.pop()
        out.append(stack[-1][1] if stack else "no host span")
    return out


def reduce(path, window_span: str = "bench.window") -> Optional[Dict]:
    """Device numbers of the traced window, or ``None`` where the trace has
    no window span or no device operation inside it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    threads: List[List[Tuple[float, float, str]]] = []
    devices: Dict[str, List[Tuple[float, float, str, bool]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                threads.append([(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name) for ev in line.events])
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = devices.setdefault(plane.name, [])
                for ev in line.events:
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                short_name(ev.name), is_kernel(ev.name)))
    # the thread that opened the window span is the benchmark's own
    spans = next((t for t in threads
                  if any(n == window_span for _, _, n in t)), None)
    if spans is None:
        return None
    w0, w1 = next((a, b) for a, b, n in spans if n == window_span)
    gaps: List[Interval] = []
    busy, kernel, glue = [], 0.0, 0.0
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for evs in devices.values():
        ivs = []
        for a, b, name, kern in evs:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
            if kern:
                kernel += (b - a) * 1e-9
            else:
                glue += (b - a) * 1e-9
        if not ivs:
            continue
        total, merged = union_length(ivs)
        busy.append(total * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    if not busy:
        return None
    gaps.sort(key=lambda g: g[0] + g[1])
    whats = _host_activity(spans, [(a + b) / 2 for a, b in gaps], window_span)
    for (a, b), what in zip(gaps, whats):
        idle[what] = idle.get(what, 0.0) + (b - a) * 1e-9
    n_dev = len(busy)
    return dict(busy_s=sum(busy) / n_dev, window_s=(w1 - w0) * 1e-9,
                kernel_s=kernel / n_dev, glue_s=glue / n_dev,
                devices=n_dev,
                ops=sorted(ops.items(), key=lambda kv: -kv[1]),
                idle=sorted(((k, v / n_dev) for k, v in idle.items()),
                            key=lambda kv: -kv[1]))
