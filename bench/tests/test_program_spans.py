"""The readers of the program's ``wait`` and ``backend`` spans, on
hand-built spans and a hand-built trace, and the spans of a traced run
on the CPU against the harness's own records of its calls."""

import time
from types import SimpleNamespace as NS

import pytest

from bench.harness import spec
from bench.tests.conftest import LIMITS, make_bench_dir

SEED = 2**31 + 23
NEW = ("engine.gemm_wait_share", "engine.syncs_per_step",
       "driver.unexplained_idle")


def _sp(name, track, t0, t1, **attrs):
    return NS(name=name, track=track, t0=t0, t1=t1, attrs=attrs or None)


def _spans():
    """Window [0, 10] on the host clock: decode steps [1, 3] and [4, 6];
    a prefill step [7, 8]; a decode step past the close [9, 11]."""
    return [
        _sp("step1", "step", 1.0, 3.0, phase="decode"),
        _sp("step2", "step", 4.0, 6.0, phase="decode"),
        _sp("step3", "step", 7.0, 8.0, phase="prefill"),
        _sp("step4", "step", 9.0, 11.0, phase="decode"),
        _sp("decode", "backend", 1.0, 2.7, rows=16),
        _sp("act_to_host", "wait", 1.1, 1.2, module="m0"),
        _sp("host_gemm", "wait", 1.5, 2.0, module="m0"),
        _sp("host_gemm", "wait", 1.8, 2.2, module="m1"),
        _sp("sample", "sample", 2.7, 2.8, rows=16),
        _sp("token_readback", "wait", 2.8, 2.9, syncs=16),
        _sp("decode", "backend", 4.0, 6.0, rows=16),
        _sp("pin", "wait", 4.1, 4.2, module="m0"),
        _sp("host_gemm", "wait", 4.5, 5.0, module="m0"),
        _sp("host_gemm", "wait", 7.2, 7.5, module="m0"),
        _sp("m0", "cpu_gemm", 8.0, 9.0),
        _sp("host_gemm", "wait", 9.5, 9.9, module="m0"),
    ]


def _ctx(spans, trace=None, offset=None):
    return NS(spans=spans, w0=0.0, w1=10.0, trace=trace, offset=offset)


def _read(name, ctx):
    return spec.Bench().metric_reader(name)(ctx)


def test_gemm_wait_share_is_the_union_over_decode_steps():
    # [1.5, 2.2] and [4.5, 5.0] over 4 s of decode steps
    assert _read("engine.gemm_wait_share", _ctx(_spans())) == \
        pytest.approx(100.0 * 1.2 / 4.0)


def test_syncs_per_step_counts_spans_or_their_syncs():
    # step 1: act_to_host, two host_gemm, 16 readbacks; step 2: two
    assert _read("engine.syncs_per_step", _ctx(_spans())) == \
        pytest.approx((3 + 16 + 2) / 2)


def test_unexplained_idle_is_idle_outside_the_serving_spans():
    # profiler clock = host clock + 100; ops [101, 102] and [104.5, 105]
    trace = {"chips": {"/device:TPU:0": [["fusion", 101.0, 1.0, ""],
                                         ["fusion.1", 104.5, 0.5, ""]]},
             "host": [], "lines": {}}
    got = _read("driver.unexplained_idle", _ctx(_spans(), trace, 100.0))
    # idle 8.5 s; held: [1, 2.9] [4, 6] [7.2, 7.5] [9.5, 9.9], 3.1 s of
    # it idle (1.5 s of the held time the chip was busy)
    assert got == pytest.approx(100.0 * (8.5 - (1.9 + 2.0 + 0.3 + 0.4
                                                - 1.5)) / 10.0)
    assert _read("driver.unexplained_idle", _ctx(_spans())) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_wait_spans_reads_nothing(name):
    """The parent program records no wait or backend spans: each reader
    returns None rather than a number."""
    old = [s for s in _spans() if s.track not in ("wait", "backend")]
    trace = {"chips": {"/device:TPU:0": []}, "host": [], "lines": {}}
    assert _read(name, _ctx(old, trace, 0.0)) is None


@pytest.fixture(scope="module")
def traced(tmp_path_factory, jax_restored):
    """One traced run of the tiny cell on the CPU, and its ``Run``."""
    from bench.harness import cell

    root = make_bench_dir(tmp_path_factory.mktemp("bench"))
    bench = spec.Bench(root=root, bench_dir=root / "bench")
    runs = []

    class Kept(cell.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)

    mp = pytest.MonkeyPatch()
    mp.setattr(cell, "Run", Kept)
    # a short window on a busy host may close before a request finished:
    # let those in flight finish for the check, as the harness allows
    mp.setattr(bench, "limits", lambda name: dict(
        LIMITS, finished_at_least=2, wait_s=120))
    try:
        out = cell.execute(bench, "tiny.closed", SEED, 1.5, True,
                           t_start=time.perf_counter(), log=lambda s: None,
                           require_tpu=False)
    finally:
        mp.undo()
    return out, runs[0]


def test_a_traced_run_reports_the_new_metrics(cpu_only, traced):
    out, _ = traced
    assert out["correct"], out["checks"]
    for name in NEW:
        assert name in out["metrics"], sorted(out["metrics"])
    assert out["metrics"]["engine.syncs_per_step"]["value"] > 0


def test_program_spans_carry_the_shapes_the_harness_records(cpu_only,
                                                           traced):
    """Call for call, the batcher's decode spans carry the rows and KV
    tokens, and the backend's prefill spans the shape, that the harness
    records around ``backend.decode`` and ``backend.prefill``."""
    _, run = traced
    decodes = [(s.attrs["rows"], s.attrs["kv_tokens"]) for s in run.spans
               if s.track == "phase" and s.name == "decode"]
    assert decodes == [(rows, sum(lens))
                       for _, _, rows, lens in run.layer.decode]
    prefills = [(s.attrs["b"], s.attrs["s"]) for s in run.spans
                if s.track == "backend" and s.name == "prefill"]
    assert prefills == [(b, s) for _, _, b, s in run.layer.prefill]
    assert len(decodes) > 10 and prefills
