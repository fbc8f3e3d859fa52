"""Engine: wire bytes the transfer stream moved inside the window over its
busy seconds (GB/s; the ``transfer`` spans' ``bytes``, host clock)."""

from bench.harness.spans import wire_rate


def read(ctx):
    spans = [s for s in ctx.spans if ctx.in_window(s.t0)]
    rate = wire_rate(spans, "transfer")
    return rate / 1e9 if rate else None
