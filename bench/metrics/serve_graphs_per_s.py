"""Graphs in the requests completed in the window, over the window's
seconds (host clock)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    w = run.window
    if "graphs" not in w or not w["seconds"]:
        return None
    return w["graphs"] / w["seconds"]
