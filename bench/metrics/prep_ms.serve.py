"""Host milliseconds per completed request in the serving engine's
``serve.group``, ``serve.merge``, ``serve.canonical``, ``serve.inputs`` and
``serve.lookup`` spans: grouping, merging, tiling and padding, input
concatenation and the program-cache lookup (``bench/spans.py``), over the
traced stretch (program span)."""
from bench import spans

PREP = ("serve.group", "serve.merge", "serve.canonical", "serve.inputs",
        "serve.lookup")


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    return spans.host_ms(run, PREP)
