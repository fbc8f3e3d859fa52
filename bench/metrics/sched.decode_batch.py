"""Scheduler: mean rows of the decode steps' sampling spans inside the
window (sequences per decode step; program tracer spans)."""

import bisect


def read(ctx):
    phases = sorted((s.t0, s.t1) for s in ctx.spans
                    if s.track == "phase" and s.name == "decode")
    starts = [a for a, _ in phases]
    rows = []
    for s in ctx.spans:
        if s.track != "sample" or not ctx.in_window(s.t0):
            continue
        i = bisect.bisect_right(starts, s.t0) - 1
        if i >= 0 and phases[i][1] >= s.t1:
            rows.append((s.attrs or {}).get("rows", 0))
    return sum(rows) / len(rows) if rows else None
