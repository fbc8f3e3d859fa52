"""Engine: share of the window's decode steps spent in the Mamba mixers'
device pieces (percent; the union of the program's spans on the ``ssm``
track — ``conv``, ``scan``, ``state_step``, each lasting until its result
is on the device — inside the ``step`` spans of phase decode, over those
steps' summed wall time; host clock).  A program without the track gives
nothing to read."""

from bench.harness.program_spans import decode_steps
from bench.harness.spans import intersect_unions, total, union_intervals


def read(ctx):
    steps = decode_steps(ctx.spans, ctx.w0, ctx.w1)
    wall = total(steps)
    ssm = union_intervals((s.t0, s.t1) for s in ctx.spans
                          if s.track == "ssm")
    if wall <= 0 or not ssm:
        return None
    held = intersect_unions(ssm, union_intervals(steps))
    return 100.0 * total(held) / wall
