"""Engine: weight bytes the host GEMM read in the window's decode phase
over its busy seconds (GB/s; the ``cpu_gemm`` spans of phase decode,
their ``bytes`` over their summed durations, host clock)."""

from bench.harness.spans import wire_rate


def read(ctx):
    spans = [s for s in ctx.spans if ctx.in_window(s.t0)
             and (s.attrs or {}).get("phase") == "decode"]
    rate = wire_rate(spans, "cpu_gemm")
    return rate / 1e9 if rate else None
