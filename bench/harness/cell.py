"""Run one cell once and build its result line."""

from __future__ import annotations

import json
import os
import shutil
import time
from typing import Callable, Dict, Optional

import numpy as np

from bench.harness import check as check_lib
from bench.harness import spec as spec_lib
from bench.harness import trace as trace_lib
from bench.harness.serve import Run, configure_jax, seed_key
from bench.harness.spans import CompileClock


class Context:
    """What a metric reader may read.  Host times are ``perf_counter``
    seconds; ``w0``/``w1`` bound the measured window."""

    def __init__(self, run: Run, clock: CompileClock, trace: Optional[Dict]):
        self.records = run.records
        self.w0, self.w1 = run.w0, run.w1
        self.setup_s = run.w0 - run.t_start
        self.counts = run.counts            # the configuration's work counts
        self.peaks = run.peaks
        self.layer = run.layer
        self.spans = getattr(run, "spans", [])
        self.events = getattr(run, "events", [])
        self.clock = clock
        self.trace = trace                  # extracted device trace or None
        self.offset = None                  # profiler clock - perf_counter
        self.summary = None                 # reduce_trace() of the window
        if trace is not None:
            self.offset = trace_lib.clock_offset(trace, run.t_sync)
            host = [(s.track, s.t0 + self.offset, s.t1 + self.offset)
                    for s in self.spans
                    if s.track in ("cpu_gemm", "transfer", "pin")]
            self.summary = trace_lib.reduce_trace(
                trace, self.w0 + self.offset, self.w1 + self.offset,
                host_spans=host)

    def in_window(self, t: float) -> bool:
        return self.w0 <= t <= self.w1

    def window_tokens(self) -> int:
        """Output tokens delivered to clients inside the window."""
        return sum(1 for r in self.records for t in r.stamps
                   if self.in_window(t))

    def itl_gaps(self) -> np.ndarray:
        """Every gap between consecutive tokens of one request, both
        tokens inside the window (seconds)."""
        gaps = []
        for r in self.records:
            ts = [t for t in r.stamps if self.in_window(t)]
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        return np.asarray(gaps, np.float64)

    def kernel_time(self, pattern: str):
        """(seconds, calls) of the device operations matching ``pattern``
        that started in the window; None without a trace."""
        if self.trace is None:
            return None
        return trace_lib.kernel_time(self.trace, self.w0 + self.offset,
                                     self.w1 + self.offset, pattern)


def _correctness(run: Run, log, control: bool = False) -> Dict:
    """Compare a sample of what was served with the plain reference (and,
    for calibration, the configuration's control in the program's
    place)."""
    import jax

    lim = run.limits
    picked = check_lib.sample(run.records, run.seed,
                              int(lim["sample_tokens"]),
                              int(lim["sample_requests"]))
    limits = {k: float(lim[k]) for k in check_lib.NUMBERS}
    if not picked:
        log("check: no finished request to compare")
        return {"correct": False, "limits": limits,
                "readings": {k: float("nan") for k in limits}}
    ref = run.bench.reference(run.conf["reference"])
    w = run.make_weights(seed_key(run.seed))

    def logits_fn(tokens):
        return ref.logits(w, jax.numpy.asarray(tokens), conf=run.conf)

    def control_fn(tokens):
        return ref.logits(w, jax.numpy.asarray(tokens), conf=run.conf,
                          control=run.conf["control"])

    t0 = time.perf_counter()
    got = check_lib.compare(logits_fn, picked,
                            control_fn if control else None)
    del w
    readings = got["program"]
    log(f"check: {len(picked)} requests, "
        f"{sum(len(r.tokens) for r in picked)} served tokens (longest "
        f"{max(len(r.tokens) for r in picked)}), reference "
        f"{time.perf_counter() - t0:.3f} s")
    out = {"correct": check_lib.judge(readings, limits),
           "readings": readings, "limits": limits}
    if control:
        out["control"] = got["control"]
        log(f"control ({run.conf['control']}): {got['control']}")
    return out


def execute(bench: spec_lib.Bench, cell: str, seed: int, seconds: float,
            trace: bool, *, t_start: float, log: Callable[[str], None],
            require_tpu: bool = True, control: bool = False) -> Dict:
    """One run: set-up, warm-up, the window, the check, the metrics."""
    run = Run(bench, cell, seed, seconds, trace, t_start=t_start, log=log,
              require_tpu=require_tpu)
    configure_jax(str(bench.root))
    run.device()
    with CompileClock() as clock:
        run.build()
        run.warm_up()
        t_warm = time.perf_counter()
        run.offer_load()
        run.close()
        t_closed = time.perf_counter()
        c_set = clock.count(run.t_start, run.w0)
        c_win = clock.count(run.w0, run.w1)
        log(f"set-up {run.w0 - run.t_start:.3f} s (warm-up ended at "
            f"{t_warm - run.t_start:.3f} s): {c_set['compiles']} compiles "
            f"({c_set['compile_s']:.3f} s), persistent cache hits "
            f"{c_set['hits']} misses {c_set['misses']}")
        log(f"window {run.w1 - run.w0:.3f} s: {c_win['compiles']} compiles, "
            f"cache hits {c_win['hits']} misses {c_win['misses']}; "
            f"closed at +{t_closed - run.w1:.3f} s")
        done = [r for r in run.records if r.done]
        log(f"finished: {len(done)} requests "
            f"({sum(r.stamps[-1] <= run.w1 for r in done)} by the close), "
            f"waited {run.t_drained - run.w1:.3f} s after it")
        extracted = None
        if trace:
            path = trace_lib.find_xplane(run.trace_dir)
            extracted = trace_lib.extract(path)
            log(f"trace: {os.path.getsize(path)} bytes, planes "
                f"{json.dumps({k: v[:8] for k, v in extracted['lines'].items()})}")
            shutil.rmtree(run.trace_dir, ignore_errors=True)
            os.makedirs(run.trace_dir, exist_ok=True)
            with open(os.path.join(run.trace_dir, "extracted.json"), "w") as f:
                json.dump(trace_lib.window_only(
                    extracted, run.w0 - run.t_sync, run.w1 - run.t_sync), f)
        ctx = Context(run, clock, extracted)
        verdict = _correctness(run, log, control)
    specs = bench.metric_specs(cell, trace)
    metrics = spec_lib.read_metrics(bench, specs, ctx)
    attempted, failed = _counts(run)
    device = dict(run.device_info)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    out = {"correct": bool(verdict["correct"]), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        s = ctx.summary
        device["busy_s"] = s["busy_s"]
        device["window_s"] = s["window_s"]
        out["breakdown"] = {"device_ops": s["device_ops"],
                            "idle_gaps": s["idle_gaps"]}
    if control:
        out["control"] = verdict["control"]
    out["checks"] = {k: {"value": verdict["readings"][k],
                         "limit": verdict["limits"][k]}
                     for k in verdict["limits"]}
    return out


def _counts(run: Run):
    """Requests attempted in the window — every request in flight during
    it — and those that failed.  A request that errs stops the run, so a
    run that reports counts has none."""
    live = [r for r in run.records if r.submit is not None
            and r.submit <= run.w1
            and not (r.done and r.stamps[-1] < run.w0)]
    return len(live), 0
