"""Device: model FLOPs of every token the program processed inside the
window (prompt chunks and decode rows, through every layer's linears
and, for the rows whose logits were computed, the output head) over the
window times the chip's bfloat16 peak, in percent.  Attention scores
are not counted.  Bounds every kernel's share from above: a PR that
takes a kernel off the path still shows here."""

from bench.harness.flops import model_flops


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    flops = 0.0
    for t0, _, b, s in ctx.layer.prefill:
        if ctx.in_window(t0):
            flops += model_flops(ctx.counts, b * s, b)
    for t0, _, rows, _ in ctx.layer.decode:
        if ctx.in_window(t0):
            flops += model_flops(ctx.counts, rows, rows)
    window = ctx.summary["window_s"]
    return 100.0 * flops / (window * ctx.peaks["bf16_flops_per_s"])
