"""Trace and backend-compile events (``jax.monitoring``) inside the
measured window (program counter)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    return run.window.get("compiles")
