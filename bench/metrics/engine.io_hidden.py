"""Engine: share of the pin and transfer streams' busy time during which
host GEMM or device compute also ran, over the decode steps inside the
window (``compute_overlap``'s I/O-hidden fraction; program tracer spans)."""

from bench.harness.spans import io_hidden


def read(ctx):
    steps = [(s.t0, s.t1) for s in ctx.spans
             if s.track == "step" and (s.attrs or {}).get("phase") == "decode"
             and ctx.in_window(s.t0) and ctx.in_window(s.t1)]
    hid, busy = io_hidden(ctx.spans, steps)
    return hid / busy if busy > 0 else None
