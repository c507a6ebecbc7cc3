"""Device milliseconds per forward in the operations, not Mosaic kernels,
that the scheduled program emits under its ``zipper.vertex`` named scope
(``bench/spans.py``), over the traced stretch (device trace)."""
from bench import spans


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    return spans.device_ms(run, "vertex")
