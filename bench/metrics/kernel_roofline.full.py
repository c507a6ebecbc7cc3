"""Least time for the aggregation's work over the kernels' device time, in
%.  The work comes from V, E, F and the layer equations
(``bench/configs/<model>.py`` ``kernel_work``), never from padded or tiled
shapes; the least time is the larger of FLOPs over peak FLOP/s and bytes
over HBM bandwidth (``bench/peaks.py``).  At these sizes the bytes bound
it (device trace)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    t, w, work = run.trace, run.trace_window, run.work
    if not t or not w or not w["n"] or t["kernel_s"] <= 0 \
            or "kernel_bytes" not in work:
        return None
    least = max(work["kernel_flops"] / run.peaks["flops_per_s"],
                work["kernel_bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (t["kernel_s"] / w["n"])
