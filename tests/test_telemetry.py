"""repro.telemetry: tracer, metrics registry, Chrome export, overlap
math, and trace-driven alpha recalibration (docs/OBSERVABILITY.md)."""
import json
import threading

import numpy as np
import pytest

from repro.core.engine import StreamStats
from repro.telemetry import (MetricsRegistry, NULL_TRACER, OverlapReport,
                             Span, Tracer, as_tracer, compute_overlap,
                             measured_speeds, recalibrate_alpha,
                             to_chrome_trace, validate_chrome_trace,
                             write_chrome_trace)
from repro.telemetry.overlap import (intersect_unions, total,
                                     union_intervals)
from repro.telemetry.tracer import _NULL_SPAN


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_records_interval_and_attrs():
    tr = Tracer()
    with tr.span("work", track="cpu_gemm", bytes=1024):
        pass
    (s,) = tr.spans()
    assert s.name == "work" and s.track == "cpu_gemm"
    assert s.attrs == {"bytes": 1024}
    assert s.t1 >= s.t0 and s.dur == s.t1 - s.t0


def test_span_late_attr_binding():
    """A step span can learn its phase after the work ran."""
    tr = Tracer()
    with tr.span("step1", track="step") as sp:
        sp.set(phase="decode")
    (s,) = tr.spans()
    assert s.attrs == {"phase": "decode"}


def test_event_and_track_defaults():
    tr = Tracer()
    tr.set_track("sched")
    tr.event("preempt", rid=3)             # thread-default track
    tr.event("admit", track="other")       # explicit wins
    evs = tr.events_list()
    assert [(e.name, e.track) for e in evs] == \
        [("preempt", "sched"), ("admit", "other")]
    assert evs[0].attrs == {"rid": 3}


def test_mark_scopes_snapshot():
    tr = Tracer()
    with tr.span("old", track="t"):
        pass
    m = tr.mark()
    with tr.span("new", track="t"):
        pass
    assert [s.name for s in tr.spans(since=m)] == ["new"]
    assert [s.name for s in tr.spans(track="t")] == ["old", "new"]


def test_ring_wrap_drops_oldest():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}", track="t"):
            pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped() == 6
    tr.clear()
    assert tr.spans() == [] and tr.dropped() == 0


def test_threads_get_own_buffers():
    tr = Tracer()

    def work(i):
        with tr.span(f"w{i}", track=f"trk{i}"):
            pass

    ths = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    spans = tr.spans()
    assert sorted(s.name for s in spans) == ["w0", "w1", "w2", "w3"]
    assert sorted(s.track for s in spans) == \
        ["trk0", "trk1", "trk2", "trk3"]


def test_disabled_tracer_is_free_and_inert():
    tr = Tracer(enabled=False)
    assert not tr and not NULL_TRACER
    # the no-op span is one shared object: no per-call allocation
    assert tr.span("x", track="t") is _NULL_SPAN
    assert NULL_TRACER.span("y") is _NULL_SPAN
    with tr.span("x", track="t") as sp:
        sp.set(phase="decode")          # no-op, no error
    tr.event("e", track="t")
    assert tr.spans() == [] and tr.events_list() == []


def test_as_tracer_normalizes():
    tr = Tracer()
    assert as_tracer(tr) is tr
    assert as_tracer(False) is NULL_TRACER
    assert as_tracer(None) is NULL_TRACER
    built = as_tracer(True)
    assert isinstance(built, Tracer) and built.enabled


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_instruments():
    m = MetricsRegistry()
    m.counter("steps").inc()
    m.counter("steps").inc(2)
    m.gauge("slots").set(3)
    m.gauge("slots").set(1)
    h = m.histogram("lat", edges=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = m.snapshot()
    assert snap["steps"] == 3.0
    assert snap["slots"] == 1.0
    assert snap["lat"]["count"] == 3
    assert snap["lat"]["buckets"] == [1, 1, 1]
    assert snap["lat"]["min"] == 0.05 and snap["lat"]["max"] == 5.0
    assert snap["lat"]["mean"] == pytest.approx(5.55 / 3)


def test_metrics_misuse_raises():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")
    with pytest.raises(ValueError):
        m.counter("y").inc(-1)
    with pytest.raises(ValueError):
        m.histogram("h", edges=(1.0, 1.0))


def test_absorb_maps_legacy_stats_keys():
    """Every numeric leaf of a legacy stats() dict appears in the
    snapshot under its dotted path — the supersession contract."""
    stats = {
        "executor": "batcher",               # identity: skipped
        "tokens_per_s": 12.5,
        "phase_alpha": {"decode": 0.2, "prefill": 0.9},
        "resident_bytes": 1 << 20,
        "retunes": 3,
        "stream": StreamStats(cpu=1.0, pin=0.25, trans=0.5, dev=2.0,
                              wall=4.0),
        "scheduler": {"policy": "fcfs", "preemptions": 1, "waiting": 0},
        "paged": {"page_size": 16, "pool_pages": 64, "mapped_pages": 8},
    }
    m = MetricsRegistry()
    m.absorb(stats)
    snap = m.snapshot()
    assert "executor" not in snap and "scheduler.policy" not in snap
    assert snap["tokens_per_s"] == 12.5
    assert snap["phase_alpha.decode"] == 0.2
    assert snap["phase_alpha.prefill"] == 0.9
    assert snap["resident_bytes"] == float(1 << 20)
    assert snap["retunes"] == 3.0
    assert snap["stream.cpu_s"] == 1.0 and snap["stream.pin_s"] == 0.25
    assert snap["stream.trans_s"] == 0.5 and snap["stream.dev_s"] == 2.0
    assert snap["stream.wall_s"] == 4.0
    assert snap["scheduler.preemptions"] == 1.0
    assert snap["paged.mapped_pages"] == 8.0
    # re-absorbing is idempotent (point-in-time gauges)
    m.absorb(stats)
    assert m.snapshot() == snap


# ---------------------------------------------------------------------------
# StreamStats (satellite: __add__ / utilization edge cases)
# ---------------------------------------------------------------------------

def test_stream_stats_add_sums_busy_maxes_wall():
    a = StreamStats(cpu=1.0, pin=0.5, trans=0.25, dev=2.0, wall=3.0,
                    transfers_ahead=7, transfers_on_demand=1)
    b = StreamStats(cpu=0.5, pin=0.5, trans=0.75, dev=1.0, wall=2.0,
                    transfers_ahead=2, transfers_on_demand=3)
    c = a + b
    assert (c.cpu, c.pin, c.trans, c.dev) == (1.5, 1.0, 1.0, 3.0)
    assert c.wall == 3.0                    # shared timeline: max, not sum
    assert (c.transfers_ahead, c.transfers_on_demand) == (9, 4)
    z = StreamStats() + StreamStats()
    assert (z.cpu, z.pin, z.trans, z.dev, z.wall) == (0, 0, 0, 0, 0)


def test_stream_stats_utilization_zero_wall():
    """A never-run engine must not divide by zero."""
    u = StreamStats().utilization()
    assert u == {"cpu": 0.0, "pin": 0.0, "trans": 0.0, "dev": 0.0}
    u2 = StreamStats(cpu=1.0, dev=3.0, wall=4.0).utilization()
    assert u2["cpu"] == pytest.approx(0.25)
    assert u2["dev"] == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# overlap math
# ---------------------------------------------------------------------------

def _sp(name, track, t0, t1, **attrs):
    return Span(name, track, t0, t1, attrs or None)


def test_interval_primitives():
    assert union_intervals([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == \
        [(0, 2), (3, 4)]
    assert intersect_unions([(0, 2), (3, 5)], [(1, 4)]) == \
        [(1, 2), (3, 4)]
    assert total([(0, 2), (3, 4)]) == 3.0


def test_overlap_perfectly_hidden():
    """I/O entirely under compute -> fraction 1.0."""
    spans = [_sp("t", "transfer", 1.0, 2.0),
             _sp("p", "pin", 1.2, 1.8),
             _sp("d", "device", 0.0, 4.0)]
    rep = compute_overlap(spans)
    assert rep.io_hidden_frac == pytest.approx(1.0)
    assert rep.overall.critical_path == "device"


def test_overlap_forced_serial_is_zero():
    """Streams running back-to-back (no concurrency) -> fraction ~0."""
    spans = [_sp("p", "pin", 0.0, 1.0),
             _sp("t", "transfer", 1.0, 2.0),
             _sp("c", "cpu_gemm", 2.0, 3.0),
             _sp("d", "device", 3.0, 4.0)]
    rep = compute_overlap(spans)
    assert rep.io_hidden_frac == pytest.approx(0.0)


def test_overlap_partial_and_bounds():
    # io [0,2], compute [1,3]: hidden 1 of 2 io seconds
    spans = [_sp("t", "transfer", 0.0, 2.0),
             _sp("d", "device", 1.0, 3.0)]
    rep = compute_overlap(spans)
    assert rep.io_hidden_frac == pytest.approx(0.5)
    assert 0.0 <= rep.io_hidden_frac <= 1.0
    assert rep.overall.busy == {"transfer": 2.0, "device": 2.0}
    util = rep.overall.utilization()
    assert util["transfer"] == pytest.approx(2.0 / 3.0)


def test_overlap_no_io_reports_one():
    rep = compute_overlap([_sp("d", "device", 0.0, 1.0)])
    assert rep.io_hidden_frac == 1.0        # nothing needed hiding
    empty = compute_overlap([])
    assert empty.overall.wall == 0.0 and empty.steps == []


def test_overlap_per_step_windows():
    spans = [_sp("step1", "step", 0.0, 2.0, phase="decode"),
             _sp("step2", "step", 2.0, 4.0, phase="verify"),
             _sp("t", "transfer", 0.0, 1.0),
             _sp("d", "device", 0.5, 3.5)]
    rep = compute_overlap(spans)
    assert [w.label for w in rep.steps] == ["step1", "step2"]
    assert [w.phase for w in rep.steps] == ["decode", "verify"]
    # step1 sees io [0,1] with compute [0.5,1] over it
    assert rep.steps[0].io_hidden_frac == pytest.approx(0.5)
    # step2 has no io at all
    assert rep.steps[1].io_hidden_frac == 1.0
    text = rep.render()
    assert "io hidden" in text and "step1" in text and "decode" in text


# ---------------------------------------------------------------------------
# chrome export
# ---------------------------------------------------------------------------

def test_chrome_export_schema_and_validation(tmp_path):
    tr = Tracer()
    with tr.span("a", track="pin", bytes=64):
        pass
    with tr.span("b", track="device"):
        pass
    tr.event("admit", track="sched", rid=1)
    path = tmp_path / "trace.json"
    doc = write_chrome_trace(str(path), tr)
    assert validate_chrome_trace(doc) == []
    on_disk = json.loads(path.read_text())
    assert validate_chrome_trace(on_disk) == []
    phs = [e["ph"] for e in on_disk["traceEvents"]]
    assert phs.count("X") == 2 and phs.count("i") == 1
    names = {e["args"]["name"] for e in on_disk["traceEvents"]
             if e["ph"] == "M"}
    assert {"pin", "device", "sched"} <= names
    xs = [e for e in on_disk["traceEvents"] if e["ph"] == "X"]
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)


def test_chrome_validator_catches_violations():
    doc = to_chrome_trace([_sp("a", "t", 1.0, 2.0),
                           _sp("b", "t", 1.5, 2.5)])   # same-track overlap
    probs = validate_chrome_trace(doc)
    assert any("overlaps" in p for p in probs)
    # distinct tracks may overlap freely
    ok = to_chrome_trace([_sp("a", "t1", 1.0, 2.0),
                          _sp("b", "t2", 1.5, 2.5)])
    assert validate_chrome_trace(ok) == []
    assert validate_chrome_trace({}) == \
        ["traceEvents missing or not a list"]
    bad = {"traceEvents": [{"ph": "Z", "pid": 0, "tid": 0, "name": "x"}]}
    assert any("unknown ph" in p for p in validate_chrome_trace(bad))


# ---------------------------------------------------------------------------
# trace-driven alpha recalibration
# ---------------------------------------------------------------------------

def _speed_spans(v_cpu, v_pin, v_com, n=8, nbytes=1 << 20):
    """Synthetic engine spans with exact per-stream speeds."""
    spans = []
    t = 0.0
    for i in range(n):
        for track, v in (("cpu_gemm", v_cpu), ("pin", v_pin),
                         ("transfer", v_com)):
            spans.append(_sp(f"m{i}", track, t, t + nbytes / v,
                             bytes=nbytes, phase="decode"))
            t += nbytes / v + 1e-3
    return spans


def test_measured_speeds_exact():
    spans = _speed_spans(2e9, 8e9, 4e9, n=4)
    est = measured_speeds(spans, phase="decode")
    assert est.v_cpu == pytest.approx(2e9, rel=1e-9)
    assert est.v_pin == pytest.approx(8e9, rel=1e-9)
    assert est.v_com == pytest.approx(4e9, rel=1e-9)
    assert est.n_spans == 12
    assert est.cpu_bytes == 4 << 20


def test_measured_speeds_missing_stream_raises():
    spans = [_sp("m", "cpu_gemm", 0.0, 1.0, bytes=1024)]
    with pytest.raises(ValueError, match="pin"):
        measured_speeds(spans)
    # byte-less spans don't count either
    spans += [_sp("m", "pin", 0.0, 1.0), _sp("m", "transfer", 0.0, 1.0)]
    with pytest.raises(ValueError):
        measured_speeds(spans)


def test_recalibrate_matches_direct_refine_alpha():
    """The trace-driven fit must reproduce refine_alpha on the same
    synthesized callables — identical probes, identical root."""
    from repro.core.alpha_benchmark import refine_alpha

    # crossing (1-a)/v_cpu = a/v_com sits at 0.5 — inside refine_alpha's
    # probe window around alpha0 (the solver refines locally, +/- gamma)
    v_cpu, v_pin, v_com = 2e9, 12e9, 2e9
    spans = _speed_spans(v_cpu, v_pin, v_com)
    alpha0 = 0.52
    fit = recalibrate_alpha(spans, alpha0, phase="decode")

    est = measured_speeds(spans, phase="decode")
    B = float(est.cpu_bytes + max(est.pin_bytes, est.trans_bytes))
    ref = refine_alpha(lambda a: (1 - a) * B / est.v_cpu,
                       lambda a: max(a * B / est.v_pin,
                                     a * B / est.v_com),
                       alpha0)
    assert fit.alpha == pytest.approx(ref.alpha, abs=1e-9)
    assert fit.predicted_time == pytest.approx(ref.predicted_time,
                                               rel=1e-9)
    # the analytic crossing for these speeds: (1-a)/v_cpu = a/v_com
    a_star = (1 / v_cpu) / (1 / v_cpu + 1 / v_com)
    assert fit.alpha == pytest.approx(a_star, abs=0.02)


def test_recalibrate_scale_invariant_in_bytes():
    spans = _speed_spans(2e9, 10e9, 5e9)
    f1 = recalibrate_alpha(spans, 0.4)
    f2 = recalibrate_alpha(spans, 0.4, bytes_per_step=123456789.0)
    assert f1.alpha == pytest.approx(f2.alpha, abs=1e-9)


# ---------------------------------------------------------------------------
# live engine + backend integration
# ---------------------------------------------------------------------------

def test_engine_emits_stream_spans(rng):
    """A traced hetegen linear produces byte-carrying spans on all four
    stream tracks, and those spans recalibrate."""
    import jax.numpy as jnp

    from repro.core import HeteGenEngine, ModulePlan

    names = [f"m{i}" for i in range(4)]
    W = {n: rng.standard_normal((96, 256)).astype(np.float32)
         for n in names}
    plan = [ModulePlan(n, "g", "hetegen", 0.5) for n in names]
    tr = Tracer()
    eng = HeteGenEngine(W, plan, tracer=tr, trace_phase="decode")
    eng.warm_prefetch()
    x = jnp.asarray(rng.standard_normal((2, 96)).astype(np.float32))
    for n in names:
        eng.linear(x, n)
    eng.close()

    spans = tr.spans()
    by_track = {t: [s for s in spans if s.track == t]
                for t in ("pin", "transfer", "cpu_gemm", "device")}
    for t, ss in by_track.items():
        assert ss, f"no spans on {t}"
    for t in ("pin", "transfer", "cpu_gemm"):
        assert all((s.attrs or {}).get("bytes", 0) > 0
                   for s in by_track[t]), t
        assert all((s.attrs or {}).get("phase") == "decode"
                   for s in by_track[t]), t
    # the trace is exportable and internally consistent
    assert validate_chrome_trace(to_chrome_trace(spans)) == []
    # and dense spans feed the recalibrator
    fit = recalibrate_alpha(spans, 0.5, phase="decode")
    assert 0.0 <= fit.alpha <= 1.0


def test_stream_span_links(rng):
    """pin -> transfer -> device spans of one module/step share a seq
    attr, so the trace shows which pin fed which transfer."""
    import jax.numpy as jnp

    from repro.core import HeteGenEngine, ModulePlan

    names = [f"m{i}" for i in range(3)]
    W = {n: rng.standard_normal((96, 256)).astype(np.float32)
         for n in names}
    plan = [ModulePlan(n, "g", "hetegen", 0.5) for n in names]
    tr = Tracer()
    eng = HeteGenEngine(W, plan, tracer=tr, trace_phase="decode")
    eng.warm_prefetch()
    x = jnp.asarray(rng.standard_normal((2, 96)).astype(np.float32))
    n_steps = 3
    for _ in range(n_steps):
        for n in names:
            eng.linear(x, n)
    eng.close()

    spans = tr.spans()
    for n in names:
        linked = {}
        for track in ("pin", "transfer", "device"):
            seqs = [(s.attrs or {}).get("seq") for s in spans
                    if s.track == track
                    and (s.attrs or {}).get("module", s.name) == n]
            assert all(q is not None for q in seqs), (n, track)
            linked[track] = seqs
        # every step's transfer/device span names the pin that fed it:
        # the same seq appears once per stream, in the same order (the
        # tail may hold one extra transfer: the DMA the last linear sent
        # ahead for a step that never ran)
        assert linked["device"] == list(range(n_steps))
        assert linked["transfer"][:n_steps] == linked["device"]
        assert len(linked["transfer"]) <= n_steps + 1
        # pins are distinct and cover every transfer (the tail may hold
        # one extra: the wrap-around prefetch of a step that never ran)
        assert len(set(linked["pin"])) == len(linked["pin"])
        assert set(linked["transfer"]) <= set(linked["pin"])


def test_traced_batcher_token_identical(rng):
    """Tracing must be observation only: same tokens with and without."""
    import jax

    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving.batcher import ContinuousBatcher

    cfg = get_config("tiny")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (5, 8)]

    ref = ContinuousBatcher(cfg, params, max_slots=2, max_len=64)
    ref_ids = [ref.submit(p, 6) for p in prompts]
    ref_out = ref.run_until_done()

    tr = Tracer()
    traced = ContinuousBatcher(cfg, params, max_slots=2, max_len=64,
                               tracer=tr)
    tr_ids = [traced.submit(p, 6) for p in prompts]
    tr_out = traced.run_until_done()

    for a, b in zip(ref_ids, tr_ids):
        assert ref_out[a] == tr_out[b]
    # the traced run recorded its steps and phases
    steps = tr.spans(track="step")
    assert steps and all((s.attrs or {}).get("phase") for s in steps)
    assert tr.spans(track="phase")
    assert tr.spans(track="sample")
    assert validate_chrome_trace(
        to_chrome_trace(tr.spans(), tr.events_list())) == []
    # serve.* metrics counted every token once
    snap = traced.metrics.snapshot()
    assert snap["serve.tokens"] == float(sum(len(o)
                                             for o in tr_out.values()))
    assert snap["serve.steps"] == len(steps)


def test_llm_facade_trace_and_metrics(rng):
    """LLM(trace=True): scheduler events, metrics() superset of stats(),
    overlap report bounded."""
    import jax

    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving.api import LLM

    cfg = get_config("tiny")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [list(rng.integers(0, cfg.vocab_size, 6)) for _ in range(3)]
    with LLM(cfg, params, max_slots=2, max_len=64, trace=True) as llm:
        for p in prompts:
            llm.submit(p, 5)
        outs = llm.drain()
        assert all(len(o.tokens) == 5 for o in outs.values())
        rep = llm.overlap_report()
        assert isinstance(rep, OverlapReport)
        assert 0.0 <= rep.io_hidden_frac <= 1.0
        snap = llm.metrics()
        st = llm.stats()
    # scheduler admissions/finishes were recorded as instant events
    admits = [e for e in llm.tracer.events_list(track="sched")
              if e.name == "admit"]
    finishes = [e for e in llm.tracer.events_list(track="sched")
                if e.name == "finish"]
    assert len(admits) == 3 and len(finishes) == 3
    # metrics() carries the legacy stats() numeric leaves, namespaced
    assert snap["scheduler.preemptions"] == \
        float(st["scheduler"]["preemptions"])
    assert snap["serve.tokens"] == 15.0
    assert snap["tokens_per_s"] == pytest.approx(st["tokens_per_s"])


# ---------------------------------------------------------------------------
# wait and backend spans; profiler annotations
# ---------------------------------------------------------------------------

WAIT_KINDS = ("act_to_host", "pin", "transfer", "device_sync", "host_gemm")


@pytest.fixture(scope="module")
def split_setup():
    """An OPT block wide enough that alpha 0.5 splits every linear into
    one 128-column device tile and a host share."""
    import dataclasses

    import jax

    from repro.configs import get_config, reduced
    from repro.models import model as M

    cfg = dataclasses.replace(
        reduced(get_config("opt-125m"), layers=2), name="opt-split",
        d_model=256, n_heads=4, head_dim=64, d_ff=512)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(1))


def _split_backend(split_setup, tracer, batch=2):
    from repro.core.hw import PAPER_A10
    from repro.serving.backends import HeteGenBackend

    cfg, params = split_setup
    return HeteGenBackend(cfg, params, hw=PAPER_A10, budget_bytes=0,
                          batch=batch, alpha_override=0.5, tracer=tracer)


def _serve(split_setup, tracer, prompts, max_new=4):
    from repro.serving.batcher import ContinuousBatcher

    cfg, _ = split_setup
    be = _split_backend(split_setup, tracer)
    with ContinuousBatcher(cfg, backend=be, own_backend=True, max_slots=2,
                           max_len=64, paged=True, tracer=tracer) as b:
        ids = [b.submit(p, max_new) for p in prompts]
        out = b.run_until_done()
        order = list(be.engine.order)
    return [out[i] for i in ids], order


def _prompts(split_setup, rng):
    cfg, _ = split_setup
    return [list(rng.integers(0, cfg.vocab_size, n)) for n in (5, 9)]


def test_split_linear_waits_once_each_per_decode_step(split_setup, rng):
    """Every split linear of a traced decode step waits exactly once on
    each stream, in spans carrying module and phase, except that only
    the engine's first pass waits on the pin: from the second step on
    each shard's DMA is issued one linear ahead, and the transfer thread
    acquires its pinned slot; the step's token
    readback is one span counting a sync per row; the decode phase span
    carries the step's rows and KV tokens from host-side state."""
    tr = Tracer()
    _, order = _serve(split_setup, tr, _prompts(split_setup, rng))
    phases = tr.spans(track="phase")
    decodes = [s for s in phases if s.name == "decode"]
    # prompts of 5 and 9 tokens, one token from each prefill, then each
    # decode step attends to kv_len + 1 = prompt + generated positions
    assert [(s.attrs["rows"], s.attrs["kv_tokens"]) for s in decodes] == \
        [(2, 16), (2, 18), (2, 20)]
    waits = tr.spans(track="wait")
    for i, d in enumerate(decodes):
        inside = [s for s in waits if d.t0 <= s.t0 and s.t1 <= d.t1]
        assert all(s.attrs["phase"] == "decode" for s in inside)
        counts = {}
        for s in inside:
            key = (s.attrs["module"], s.name)
            counts[key] = counts.get(key, 0) + 1
        kinds = [k for k in WAIT_KINDS if i == 0 or k != "pin"]
        assert counts == {(m, k): 1 for m in order for k in kinds}
        back = [s for s in waits if s.name == "token_readback"
                and d.t1 <= s.t0 and s.attrs["phase"] == "decode"]
        assert back and back[0].attrs == {"module": "batcher",
                                          "phase": "decode", "syncs": 2}
    assert not any(s.attrs.get("miss") for s in waits if s.name == "pin"
                   and s.attrs["phase"] == "decode" and s.t0 > decodes[1].t0)
    assert validate_chrome_trace(to_chrome_trace(tr.spans())) == []


def test_engine_builds_are_spanned(split_setup):
    """retune and a recalibration rebuild each record one build span on
    the backend track, with the plan's shape and the host bytes copied
    (none: the backend holds its weights (out, in), so every share of an
    fp split is a view of them)."""
    tr = Tracer()
    be = _split_backend(split_setup, tr)
    m = tr.mark()
    be.retune(3)
    (b1,) = tr.spans(since=m, track="backend")
    assert b1.name == "build"
    assert b1.attrs == {"phase": "decode", "batch": 3, "tokens_per_seq": 1,
                        "alpha": be.policies["decode"].alpha,
                        "host_bytes": 0}
    m = tr.mark()
    be._apply_alpha("decode", 0.5)
    (b2,) = tr.spans(since=m, track="backend")
    assert b2.attrs == dict(b1.attrs, alpha=0.5)
    be.close()


def test_traced_offload_batcher_token_identical(split_setup, rng):
    """Wait, backend and phase-shape spans observe only: an offloaded
    batcher serves the same tokens with and without the tracer."""
    prompts = _prompts(split_setup, rng)
    ref, _ = _serve(split_setup, NULL_TRACER, prompts)
    tr = Tracer()
    got, _ = _serve(split_setup, tr, prompts)
    assert got == ref
    names = {s.name for s in tr.spans(track="backend")}
    assert {"build", "prefill", "decode"} <= names


def test_null_tracer_constructs_no_annotation(monkeypatch, rng):
    """The disabled path never builds a profiler annotation; the enabled
    one builds one per span."""
    import jax.numpy as jnp

    import repro.telemetry.tracer as tracer_mod
    from repro.core import HeteGenEngine, ModulePlan

    made = []

    class Counting:
        def __init__(self, name, **kw):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Counting)
    W = {n: rng.standard_normal((96, 256)).astype(np.float32)
         for n in ("a", "b")}
    plan = [ModulePlan(n, "g", "hetegen", 0.5) for n in W]
    x = jnp.ones((2, 96), jnp.float32)
    eng = HeteGenEngine(W, plan, tracer=NULL_TRACER, trace_phase="decode")
    for n in W:
        eng.linear(x, n)
    eng.close()
    assert made == []
    tr = Tracer()
    eng = HeteGenEngine(W, plan, tracer=tr, trace_phase="decode")
    for n in W:
        eng.linear(x, n)
    eng.close()
    assert sorted(made) == sorted(f"{s.track}:{s.name}"
                                  for s in tr.spans())


def test_spans_land_in_the_profile_on_its_clock(tmp_path, rng):
    """Under jax.profiler every program span appears as a host
    annotation ``<track>:<name>`` with its scalar attributes, on the
    thread that ran it, starting where the span started (within 1 ms,
    through one clock-sync annotation)."""
    import glob
    import time

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.core import HeteGenEngine, ModulePlan

    W = {n: rng.standard_normal((96, 256)).astype(np.float32)
         for n in ("a", "b", "c")}
    plan = [ModulePlan(n, "g", "hetegen", 0.5) for n in W]
    x = jnp.ones((2, 96), jnp.float32)
    tr = Tracer()
    eng = HeteGenEngine(W, plan, tracer=tr, trace_phase="decode")
    eng.linear(x, "a")                    # compile outside the profile
    tr.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test.clock_sync"):
            t_sync = time.perf_counter()
        for n in W:
            eng.linear(x, n)
    finally:
        jax.profiler.stop_trace()
    eng.close()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = [(ev.name, ev.start_ns * 1e-9, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    (sync,) = [t for name, t, _ in events if name == "test.clock_sync"]
    offset = sync - t_sync
    spans = tr.spans()
    assert {s.track for s in spans} >= {"wait", "pin", "transfer",
                                        "cpu_gemm", "device"}
    for s in spans:
        name = f"{s.track}:{s.name}"
        cands = [(abs(t - (s.t0 + offset)), st) for n, t, st in events
                 if n == name]
        assert cands, name
        err, stats = min(cands, key=lambda c: c[0])
        assert err < 1e-3, (name, err)
        assert stats.get("module") == s.attrs["module"]
