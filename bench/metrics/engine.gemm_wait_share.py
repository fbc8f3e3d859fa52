"""Engine: share of the window's decode steps that the serving thread
spent waiting for the host GEMM (percent; the union of the program's
``wait/host_gemm`` spans inside the ``step`` spans of phase decode, over
those steps' summed wall time; host clock)."""

from bench.harness.program_spans import decode_steps, wait_share


def read(ctx):
    share = wait_share(ctx.spans, decode_steps(ctx.spans, ctx.w0, ctx.w1),
                       "host_gemm")
    return None if share is None else 100.0 * share
