"""Run one cell once: set-up, the measured window, the optional traced
stretch, the check, and the metrics.

A cell's loop (``bench/loops/<loop>.py``, named by its traffic file)
provides ``setup(ctx)``, ``window(state, seconds, annotate)``,
``work(state)``, ``check_outputs(state, window, limits)``, and for the
control and the fault tests ``control(state)`` and ``fault(state, kind)``.
Each metric's reader (``bench/metrics/<metric>.py``) turns the
:class:`Run` into one number, or ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from . import check, manifest, peaks, xplane
from .manifest import BenchError

WINDOW_SPAN = "bench.window"
#: length of the traced stretch that follows the measured window in a
#: ``--trace 1`` run: long enough for some hundred forwards or requests,
#: short enough that the trace stays small and reads in seconds
TRACE_SECONDS = 3.0
TRACE_DIR = manifest.ROOT / "bench_out" / "trace"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def _counting_compiles(loop, state, seconds: float) -> Dict:
    """The measured window, with the trace and compile events inside it
    counted (``jax.monitoring``) and Python's garbage collections timed."""
    events: List[str] = []
    gc_ms: List[float] = []
    gc_t0 = [0.0]

    def listener(event, secs, **kw):
        if event in COMPILE_EVENTS:
            events.append(event)

    def on_gc(phase, info):
        if phase == "start":
            gc_t0[0] = time.perf_counter()
        else:
            gc_ms.append(1e3 * (time.perf_counter() - gc_t0[0]))

    jax.monitoring.register_event_duration_secs_listener(listener)
    gc.callbacks.append(on_gc)
    try:
        win = loop.window(state, seconds)
    finally:
        gc.callbacks.remove(on_gc)
        jax.monitoring.unregister_event_duration_listener(listener)
    win["compiles"] = len(events)
    win["gc_ms"] = gc_ms
    return win


def _longest(win: Dict, k: int = 3) -> str:
    """The ``k`` longest iterations of a window, each with when it ended."""
    ends = np.asarray(win["ends"])
    if not len(ends):
        return "none"
    took = np.diff(np.concatenate([[win["t0"]], ends]))
    return ", ".join(f"{1e3 * took[i]:.1f} ms ending at "
                     f"{ends[i] - win['t0']:.2f} s"
                     for i in np.argsort(took)[::-1][:k])


class Context:
    """What a loop's set-up gets: the cell's files, the seed, and the
    set-up clock."""

    def __init__(self, cfg, traffic, seed, model, log):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.model, self.log = model, log

    @contextlib.contextmanager
    def step(self, name: str):
        t0 = time.perf_counter()
        yield
        self.log(f"[setup] {name}: {time.perf_counter() - t0:.3f} s")

    def subseed(self, purpose: str) -> int:
        """A 31-bit seed for one purpose, drawn from ``--seed`` (any size)."""
        ss = np.random.SeedSequence([self.seed % (1 << 63),
                                     sum(map(ord, purpose))])
        return int(ss.generate_state(1)[0] & 0x7FFFFFFF)

    def require_params(self, program_params: Dict) -> None:
        want = {k: tuple(v) for k, v in self.cfg["params"].items()}
        got = {k: tuple(v) for k, v in program_params.items()}
        if want != got:
            raise BenchError(f"the program's parameters {got} are not the "
                             f"configuration's {want}")

    def weights_and_features(self, n_vertices: int):
        """Weights (normal / sqrt(fan_in)) and features (standard normal)
        drawn from ``--seed`` on the device, in one jitted call."""
        import jax.numpy as jnp

        shapes = tuple((k, tuple(v)) for k, v in
                       sorted(self.cfg["params"].items()))
        dim = self.cfg["in_dim"]

        @jax.jit
        def make(key):
            keys = jax.random.split(key, len(shapes) + 1)
            params = {name: jax.random.normal(k, shape, jnp.float32)
                      / math.sqrt(shape[0])
                      for k, (name, shape) in zip(keys[1:], shapes)}
            x = jax.random.normal(keys[0], (n_vertices, dim), jnp.float32)
            return params, x

        return make(jax.random.PRNGKey(self.subseed("weights")))


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    setup_s: float
    window: Dict
    work: Dict
    peaks: Dict
    trace: Optional[Dict] = None
    trace_window: Optional[Dict] = None


def _traced(loop, state, seconds: float, log):
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            win = loop.window(state, seconds, annotate=True)
    finally:
        jax.profiler.stop_trace()
    t0 = time.perf_counter()
    red = xplane.reduce(xplane.newest_trace(TRACE_DIR), WINDOW_SPAN)
    log(f"[trace] {win['n']} iterations in {win['seconds']:.3f} s traced; "
        f"read in {time.perf_counter() - t0:.3f} s")
    return win, red


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             overrides: Optional[Dict] = None,
             patch: Optional[Callable] = None,
             log: Callable = print) -> Dict:
    """One run of one cell; returns the result line's object.

    ``require_chip=False``, ``overrides`` (dict updates of the traffic and
    config) and ``patch(loop, state)`` are for the CPU tests: they size a
    cell down, skip the look for a chip, and break the timed path."""
    man = manifest.Manifest()
    cell = man.cell(workload)
    cfg = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    limits = man.limits(workload)
    for part, over in (overrides or {}).items():
        {"config": cfg, "traffic": traffic}[part].update(over)

    t0 = time.perf_counter()
    devs = jax.devices()
    dev = devs[0]
    log(f"[setup] runtime start (jax.devices): {time.perf_counter() - t0:.3f} s")
    if require_chip:
        if dev.platform != "tpu":
            raise BenchError(f"no TPU: JAX runs on {dev.platform!r}; the "
                             "benchmark measures the chip and has no CPU "
                             "fallback")
        if len(devs) < cell["chips"]:
            raise BenchError(f"the cell needs {cell['chips']} chips, JAX "
                             f"sees {len(devs)}")
    pk = peaks.peaks(dev.device_kind) if require_chip else {}
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devs)}, jax "
        f"{jax.__version__}, cell {workload}, seed {seed}")

    model = man.model(cfg["model"])
    loop = man.loop(traffic["loop"])
    ctx = Context(cfg, traffic, seed, model, log)
    state = loop.setup(ctx)
    if patch is not None:
        patch(loop, state)
    setup_s = time.perf_counter() - t_start
    log(f"[setup] total {setup_s:.3f} s")

    win = _counting_compiles(loop, state, seconds)
    used = devs[:cell["chips"]]
    mem = [d.memory_stats() or {} for d in used] if require_chip else []
    log(f"[window] {win['n']} completed of {win['attempted']} in "
        f"{win['seconds']:.3f} s, {win['compiles']} compile events; longest "
        f"iterations {_longest(win)}; {len(win['gc_ms'])} garbage "
        f"collections, {sum(win['gc_ms']):.1f} ms, longest "
        f"{max(win['gc_ms'], default=0.0):.1f} ms")
    mem_peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    if mem:
        log(f"[memory] {mem[0]}")
    if require_chip and hasattr(loop, "program_memory"):
        # the allocator's peak may leave out a program's temporaries: count
        # them on top of what is in use while the program's state is alive
        t0 = time.perf_counter()
        pm = loop.program_memory(state)
        in_use = max(m.get("bytes_in_use", 0) for m in mem)
        mem_peak = max(mem_peak, in_use + pm["temp"])
        log(f"[memory] window's program: {pm}; read in "
            f"{time.perf_counter() - t0:.3f} s")

    tr_win = red = None
    if trace:
        tr_win, red = _traced(loop, state, TRACE_SECONDS, log)
        if tr_win.get("failed"):
            win["failed"] += tr_win["failed"]
        tr_win.pop("outputs", None)
        tr_win.pop("results", None)

    work = loop.work(state)
    t0 = time.perf_counter()
    cs = loop.check_outputs(state, win, limits)
    log(f"[check] reference and comparison {time.perf_counter() - t0:.3f} s")
    correct = check.passed(cs) and win["failed"] == 0

    run = Run(setup_s=setup_s, window=win,
              work=work, peaks=pk, trace=red, trace_window=tr_win)
    metrics = {}
    for m in man.metrics_of(workload, per_layer=trace):
        value = man.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": bool(correct), "attempted": int(win["attempted"]),
              "failed": int(win["failed"]), "metrics": metrics,
              "device": device}
    if trace and red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {
            "device_ops": [[n, v] for n, v in red["ops"][:10]],
            "idle_gaps": [[n, v] for n, v in red["idle"][:10]]}
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in cs}
    return result


def report(result: Dict) -> None:
    """The contract's ending: the compared numbers as the last lines of
    standard error, the result as the last line of standard output."""
    import json

    cs = [(n, c["value"], c["limit"]) for n, c in result["checks"].items()]
    sys.stdout.flush()
    for line in check.fmt(cs):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
