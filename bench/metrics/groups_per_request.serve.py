"""Program executions per request: the change in the server's
``stats()["batches"]`` over the change in ``stats()["requests"]`` across
the window (program counter)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    w = run.window
    if not w.get("requests_delta"):
        return None
    return w["batches_delta"] / w["requests_delta"]
