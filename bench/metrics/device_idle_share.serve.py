"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals / window), in % (device trace)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
