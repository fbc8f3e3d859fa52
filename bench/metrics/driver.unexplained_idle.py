"""Host driver: share of the window in which the chip ran no operation
(the device trace) and the serving thread was in no ``wait``, ``device``,
``sample`` or ``backend`` span of the program (percent; the spans moved
onto the profiler's clock by the clock-sync offset)."""

from bench.harness.program_spans import unexplained_idle


def read(ctx):
    if ctx.trace is None:
        return None
    ops = [(t0, t0 + dur) for chip in ctx.trace["chips"].values()
           for _, t0, dur, _ in chip]
    share = unexplained_idle(ctx.spans, ops, ctx.w0 + ctx.offset,
                             ctx.w1 + ctx.offset, ctx.offset)
    return None if share is None else 100.0 * share
