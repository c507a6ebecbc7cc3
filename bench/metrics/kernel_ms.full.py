"""Device milliseconds per forward in Mosaic kernels (ops whose HLO is a
``tpu_custom_call``), over the traced stretch (device trace)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    t, w = run.trace, run.trace_window
    if not t or not w or not w["n"] or t["kernel_s"] <= 0:
        return None
    return 1e3 * t["kernel_s"] / w["n"]
