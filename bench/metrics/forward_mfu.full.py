"""Model FLOPs per forward (from V, E, F and the layer equations) times the
forwards of the measured window, over the window's seconds and the chip's
peak FLOP/s, in % (host clock)."""


def read(run):
    """The metric's value, or ``None`` where the run has nothing to read."""
    w, work = run.window, run.work
    if "model_flops" not in work or not w.get("n") or not w["seconds"]:
        return None
    return 100.0 * work["model_flops"] * w["n"] / w["seconds"] \
        / run.peaks["flops_per_s"]
