"""Device milliseconds per forward in the typed aggregation's kernels: the
Mosaic kernel ops whose HLO instruction carries a ``pallas_call`` name
that starts ``relation_`` (``relation_transform``, ``relation_sum``), over
the traced stretch (device trace)."""

PREFIX = "%relation_"


def kernel_s(run):
    """Device seconds of the relation kernels' ops in the traced stretch,
    or ``None`` where the trace holds none."""
    t = run.trace
    if not t:
        return None
    got = [s for name, s in t["ops"]
           if name.startswith(PREFIX) and " = tpu_custom_call " in name]
    return sum(got) if got else None


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    w, s = run.trace_window, kernel_s(run)
    if s is None or not w or not w["n"]:
        return None
    return 1e3 * s / w["n"]
