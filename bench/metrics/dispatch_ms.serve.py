"""Host milliseconds per completed request in the serving engine's
``serve.dispatch`` spans: the jitted call, with its inputs' conversion to
device arrays (``bench/spans.py``), over the traced stretch (program
span)."""
from bench import spans


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    return spans.host_ms(run, ("serve.dispatch",))
