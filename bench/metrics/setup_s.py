"""Set-up seconds: from process start to the start of the measured window
(host clock)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    return run.setup_s
