"""Padded rows of the relation-grouped layout over its real rows (typed
edges), in %: ``PipelinedRunner.relation_rows`` (program counter)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    work = run.work
    if not work.get("rel_real_rows"):
        return None
    return 100.0 * work["rel_padded_rows"] / work["rel_real_rows"]
