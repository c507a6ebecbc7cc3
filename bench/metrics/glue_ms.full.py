"""Device milliseconds per forward in every operation that is not a Mosaic
kernel: runtime densify, edge scores, dense transforms, unpad (device
trace)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    t, w = run.trace, run.trace_window
    if not t or not w or not w["n"]:
        return None
    return 1e3 * t["glue_s"] / w["n"]
