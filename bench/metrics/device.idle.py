"""Device: share of the window in which no operation ran on the chip
(percent; 1 - union of the trace's operation intervals / window)."""


def read(ctx):
    if ctx.summary is None:
        return None
    s = ctx.summary
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
