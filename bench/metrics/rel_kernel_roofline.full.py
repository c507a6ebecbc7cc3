"""Least time for the typed aggregation's work over the relation kernels'
device time (``rel_kernel_ms.full``), in %.  The work comes from V, E, F,
R and the block size (``bench/configs/rgcn.py`` ``kernel_work``), never
from padded or grouped shapes; the least time is the larger of FLOPs over
peak FLOP/s and bytes over HBM bandwidth (``bench/peaks.py``) (device
trace)."""
from bench import manifest

_MS = manifest.load_module(manifest.BENCH / "metrics"
                           / "rel_kernel_ms.full.py")


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    w, work, s = run.trace_window, run.work, _MS.kernel_s(run)
    if s is None or not w or not w["n"] or "rel_kernel_bytes" not in work:
        return None
    least = max(work["rel_kernel_flops"] / run.peaks["flops_per_s"],
                work["rel_kernel_bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (s / w["n"])
