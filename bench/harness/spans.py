"""Arithmetic over host-clock spans, kept with the benchmark.

Copied from the program (``repro.telemetry.overlap`` and
``repro.telemetry.recalibrate``; ``chip_smoke.CompileClock``) so that the
yardstick cannot move with the code it measures.  Spans are anything with
``t0``, ``t1``, ``track`` and ``attrs``; times are ``time.perf_counter``
seconds.
"""

from __future__ import annotations

import threading
import time
from typing import Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]
IO_TRACKS = ("pin", "transfer")
COMPUTE_TRACKS = ("cpu_gemm", "device")


def union_intervals(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into a disjoint, sorted union; empty ones vanish."""
    out: List[Interval] = []
    for t0, t1 in sorted((a, b) for a, b in intervals if b > a):
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1] = (out[-1][0], t1)
        else:
            out.append((t0, t1))
    return out


def intersect_unions(a: Sequence[Interval],
                     b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two disjoint sorted unions."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip_union(ivs: Sequence[Interval], t0: float,
               t1: float) -> List[Interval]:
    out = []
    for a, b in ivs:
        lo, hi = max(a, t0), min(b, t1)
        if hi > lo:
            out.append((lo, hi))
    return out


def total(ivs: Sequence[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def io_hidden(spans, windows: Sequence[Interval]):
    """(seconds of I/O with compute running beside it, seconds of I/O)
    summed over ``windows`` — the I/O-hidden fraction's numerator and
    denominator, as ``compute_overlap`` defines them per step."""
    io = union_intervals((s.t0, s.t1) for s in spans
                         if s.track in IO_TRACKS)
    comp = union_intervals((s.t0, s.t1) for s in spans
                           if s.track in COMPUTE_TRACKS)
    hid = busy = 0.0
    for w0, w1 in windows:
        io_w = clip_union(io, w0, w1)
        busy += total(io_w)
        hid += total(intersect_unions(io_w, clip_union(comp, w0, w1)))
    return hid, busy


def wire_rate(spans, track: str = "transfer"):
    """Σ wire bytes / Σ busy seconds of one stream's spans (bytes/s), as
    ``measured_speeds`` computes it; None without busy time."""
    nbytes = sum((s.attrs or {}).get("bytes", 0) for s in spans
                 if s.track == track)
    busy = sum(s.t1 - s.t0 for s in spans if s.track == track)
    return nbytes / busy if busy > 0 else None


class CompileClock:
    """Backend compiles and persistent-cache hits and misses, each with the
    host time it was reported at, from JAX's monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.compiles: List[Tuple[float, float]] = []   # (time, seconds)
        self.hits: List[float] = []
        self.misses: List[float] = []
        self._lock = threading.Lock()

    def _on_duration(self, event, duration, **_):
        if event == self.COMPILE:
            with self._lock:
                self.compiles.append((time.perf_counter(), duration))

    def _on_event(self, event, **_):
        with self._lock:
            if event == self.HIT:
                self.hits.append(time.perf_counter())
            elif event == self.MISS:
                self.misses.append(time.perf_counter())

    def count(self, t0: float, t1: float) -> dict:
        """Compiles, compile seconds, hits and misses reported in [t0, t1]."""
        with self._lock:
            c = [d for t, d in self.compiles if t0 <= t <= t1]
            return {"compiles": len(c), "compile_s": sum(c),
                    "hits": sum(t0 <= t <= t1 for t in self.hits),
                    "misses": sum(t0 <= t <= t1 for t in self.misses)}

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
