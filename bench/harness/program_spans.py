"""Reading the program's own ``wait`` and ``backend`` spans.

The serving thread records one span on the ``wait`` track around each
call that blocks it on another stream or on the device (``act_to_host``,
``pin``, ``transfer``, ``device_sync``, ``host_gemm``,
``token_readback``), and the backend one span on the ``backend`` track
per prefill, decode, verify and engine build.  A program that records
neither gives these readers nothing to read: each then returns None.
Spans are anything with ``name``, ``track``, ``t0``, ``t1`` and
``attrs``, on ``time.perf_counter`` seconds.
"""

from __future__ import annotations

import bisect
from typing import List, Optional, Sequence

from bench.harness.spans import (Interval, clip_union, intersect_unions,
                                 total, union_intervals)

WAIT = "wait"
# the serving thread's spans that account for its time: waits, the
# engine's device calls, sampling, and the backend's calls and builds
SERVING = ("wait", "device", "sample", "backend")


def decode_steps(spans, w0: float, w1: float) -> List[Interval]:
    """The batcher's ``step`` spans of phase decode that lie wholly in
    [w0, w1], sorted (the steps ``engine.io_hidden`` reads)."""
    return sorted((s.t0, s.t1) for s in spans
                  if s.track == "step"
                  and (s.attrs or {}).get("phase") == "decode"
                  and w0 <= s.t0 and s.t1 <= w1)


def has_waits(spans) -> bool:
    return any(s.track == WAIT for s in spans)


def _in_steps(t: float, steps: Sequence[Interval]) -> bool:
    i = bisect.bisect_right([a for a, _ in steps], t) - 1
    return i >= 0 and t <= steps[i][1]


def syncs_per_step(spans, steps: Sequence[Interval]) -> Optional[float]:
    """Blocking host round trips per step: each ``wait`` span that starts
    inside a step counts 1, or its ``syncs`` attribute when it has one."""
    if not steps or not has_waits(spans):
        return None
    n = sum((s.attrs or {}).get("syncs", 1) for s in spans
            if s.track == WAIT and _in_steps(s.t0, steps))
    return n / len(steps)


def wait_share(spans, steps: Sequence[Interval],
               kind: str) -> Optional[float]:
    """Share of the steps' summed wall time that the serving thread spent
    in ``wait`` spans named ``kind`` (their union, clipped to the steps)."""
    wall = total(steps)
    if wall <= 0 or not has_waits(spans):
        return None
    waits = union_intervals((s.t0, s.t1) for s in spans
                            if s.track == WAIT and s.name == kind)
    return total(intersect_unions(waits, union_intervals(steps))) / wall


def unexplained_idle(spans, device_ops: Sequence[Interval], w0: float,
                     w1: float, offset: float) -> Optional[float]:
    """Share of [w0, w1] (profiler clock) in which no device operation ran
    and the serving thread was in no ``SERVING`` span; ``offset`` moves a
    span's host time onto the profiler's clock."""
    if not any(s.track in ("wait", "backend") for s in spans) or w1 <= w0:
        return None
    busy = clip_union(union_intervals(device_ops), w0, w1)
    idle, prev = [], w0
    for a, b in busy:
        if a > prev:
            idle.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        idle.append((prev, w1))
    held = clip_union(union_intervals(
        (s.t0 + offset, s.t1 + offset) for s in spans
        if s.track in SERVING), w0, w1)
    return (total(idle) - total(intersect_unions(idle, held))) / (w1 - w0)
