"""From a profiler trace to device busy time, kernel time and idle gaps.

:func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists (seconds on the profiler's clock); :func:`reduce_trace` turns
those lists into the numbers the metrics read.  The split lets the
reduction be checked on a small trace recorded on the chip and committed
(``tests/data``), with nothing but JSON.

Definitions:

* device busy: the union of the intervals in which an operation ran on a
  chip (its "XLA Ops" line), inside the traced window; averaged over chips;
* idle share: 1 - busy / window;
* a kernel's time (:func:`kernel_time`): the summed durations of the
  operations whose name or description matches the kernel's pattern (a
  regular expression);
* an operation's name in the breakdown: its HLO text up to its layout,
  e.g. ``%_paged.1 = f32[640,1,128]``;
* idle gaps: the stretches of the window in which no operation ran, each
  labelled by what the host was doing in its middle — the innermost of
  the harness's own ``bench.*`` annotations and the engine's stream spans
  (host GEMM, pin, transfer) that covers it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness.spans import clip_union, total, union_intervals

HOST_PREFIX = "bench."
SYNC_MARK = "bench.clock_sync"


def _desc(ev) -> str:
    """The string-valued stats of an event (long name, module, ...)."""
    out = []
    try:
        for st in ev.stats:
            val = st[1] if isinstance(st, tuple) else getattr(st, "value", "")
            if isinstance(val, str) and val:
                out.append(val)
    except Exception:       # stats are optional metadata
        return ""
    return " ".join(out)


def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def extract(path: str) -> Dict:
    """Device operations per chip and the harness's host annotations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    chips: Dict[str, List] = {}
    host: List = []
    lines_seen: Dict[str, List[str]] = {}
    for plane in pd.planes:
        lines = list(plane.lines)
        lines_seen[plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for ln in lines:
                if ln.name == "XLA Ops":
                    chips[plane.name] = [
                        [ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                         _desc(ev)] for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9])
    return {"chips": chips, "host": host, "lines": lines_seen}


def clock_offset(trace: Dict, t_sync: float) -> float:
    """Profiler-clock seconds minus ``perf_counter`` seconds, from the
    sync annotation the harness opened at ``perf_counter() == t_sync``."""
    marks = [h[1] for h in trace["host"] if h[0] == SYNC_MARK]
    if not marks:
        raise ValueError("trace holds no clock-sync annotation")
    return marks[0] - t_sync


def _label_at(t: float, layers: Sequence[Tuple[str, List]]) -> str:
    """Name of the first layer (innermost first) with an interval
    covering ``t``."""
    for name, ivs in layers:
        starts = [a for a, _ in ivs]
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and ivs[i][1] >= t:
            return name
    return "host_other"


def short_name(name: str) -> str:
    """An operation's HLO text up to its layout: its name and shape."""
    return name.split("{", 1)[0].strip()


def kernel_time(trace: Dict, w0: float, w1: float,
                pattern: str) -> Tuple[float, int]:
    """Summed duration and count of the operations that start inside
    [w0, w1] and whose name or description matches the regular
    expression ``pattern``."""
    rx = re.compile(pattern)
    secs, n = 0.0, 0
    for ops in trace["chips"].values():
        for name, t0, dur, desc in ops:
            if w0 <= t0 < w1 and (rx.search(name) or rx.search(desc)):
                secs += dur
                n += 1
    return secs, n


def reduce_trace(trace: Dict, w0: float, w1: float, *,
                 host_spans: Sequence[Tuple[str, float, float]] = (),
                 top: int = 10) -> Dict:
    """Busy and idle time and the breakdown, for the window [w0, w1] on
    the profiler's clock.  ``host_spans`` are extra labelled host
    intervals on that clock (the engine's streams)."""
    window = w1 - w0
    busy_per_chip, ops_s = [], {}
    busy_union = None
    for _, ops in sorted(trace["chips"].items()):
        ivs = []
        for name, t0, dur, desc in ops:
            if t0 + dur <= w0 or t0 >= w1:
                continue
            ivs.append((t0, t0 + dur))
            seen = min(t0 + dur, w1) - max(t0, w0)
            key = short_name(name)
            ops_s[key] = ops_s.get(key, 0.0) + seen
        u = clip_union(union_intervals(ivs), w0, w1)
        busy_per_chip.append(total(u))
        if busy_union is None:
            busy_union = u
    busy = sum(busy_per_chip) / max(len(busy_per_chip), 1)
    # idle gaps on the first chip, labelled by the host's activity
    gaps, prev = [], w0
    for a, b in (busy_union or []):
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    by_label: Dict[str, List] = {}
    for name, t0, dur in trace["host"]:
        if name != SYNC_MARK:
            by_label.setdefault(name[len(HOST_PREFIX):], []).append(
                (t0, t0 + dur))
    for name, t0, t1 in host_spans:
        by_label.setdefault(name, []).append((t0, t1))
    # innermost first: engine streams, then sampling/retune, then phases
    order = ["cpu_gemm", "transfer", "pin", "sample", "retune",
             "prefill", "decode"]
    layers = [(k, union_intervals(by_label[k])) for k in order
              if k in by_label]
    layers += [(k, union_intervals(v)) for k, v in by_label.items()
               if k not in order]
    idle: Dict[str, float] = {}
    for a, b in gaps:
        lab = _label_at(0.5 * (a + b), layers)
        idle[lab] = idle.get(lab, 0.0) + (b - a)
    return {
        "window_s": window,
        "busy_s": busy,
        "chips": len(busy_per_chip),
        "device_ops": sorted(([k, v] for k, v in ops_s.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def window_only(trace: Dict, rel0: float, rel1: float) -> Dict:
    """The part of an extracted trace inside a window given in seconds
    after the clock-sync mark: device operations that overlap it, and the
    harness's annotations that overlap it, with the mark itself."""
    sync = [h for h in trace["host"] if h[0] == SYNC_MARK]
    t0 = sync[0][1] + rel0
    t1 = sync[0][1] + rel1
    chips = {k: [op for op in ops if op[1] < t1 and op[1] + op[2] > t0]
             for k, ops in trace["chips"].items()}
    host = sync[:1] + [h for h in trace["host"] if h[0] != SYNC_MARK
                       and h[1] < t1 and h[1] + h[2] > t0]
    return {"chips": chips, "host": host, "lines": trace["lines"]}
