"""95th percentile (nearest rank) of request latency over every request
completed in the window, each timed from when it was issued until its
per-graph outputs were host arrays (host clock)."""


def percentile(samples, q):
    """Nearest-rank percentile; the arithmetic of the program's
    ``serve/metrics.py``, copied so that it cannot move under the
    benchmark."""
    xs = sorted(samples)
    if not xs:
        return None
    if q <= 0:
        return xs[0]
    rank = max(1, -(-len(xs) * q // 100))
    return xs[min(int(rank), len(xs)) - 1]


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    return percentile(run.window.get("latencies_ms", []), 95)
