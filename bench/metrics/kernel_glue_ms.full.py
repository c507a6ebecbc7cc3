"""Device milliseconds per forward in the operations under the scheduled
program's ``zipper.kernel`` named scope that are not Mosaic kernels: the
kernel wrappers' casts, pads and layout work (``bench/spans.py``), over the
traced stretch (device trace)."""
from bench import spans


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    return spans.device_ms(run, "kernel")
