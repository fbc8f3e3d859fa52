"""Engine: blocking host round trips per decode step inside the window
(the program's ``wait`` spans that start in a ``step`` span of phase
decode: 1 each, or the span's ``syncs``; per step)."""

from bench.harness.program_spans import decode_steps, syncs_per_step


def read(ctx):
    return syncs_per_step(ctx.spans, decode_steps(ctx.spans, ctx.w0, ctx.w1))
