"""Megabytes per completed request that the serving engine hands to the
device: the ``bytes`` arguments of its ``serve.bind`` and ``serve.dispatch``
spans (``bench/spans.py``), over the traced stretch (program span)."""
from bench import spans


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    return spans.host_mb(run, ("serve.bind", "serve.dispatch"))
