"""Milliseconds per full-graph forward: the window's milliseconds over the
forwards completed in it, each ended by ``block_until_ready`` (host
clock)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    w = run.window
    if not w.get("n"):
        return None
    return 1e3 * w["seconds"] / w["n"]
