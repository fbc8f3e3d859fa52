"""Backend seam: engines (weight partitions) built inside the window,
counted by the harness around ``HeteGenBackend.retune``.  Each build
copies every weight's partition on the host and stalls the step."""


def read(ctx):
    if ctx.trace is None:
        return None
    return sum(1 for t0, _, _ in ctx.layer.builds if ctx.in_window(t0))
