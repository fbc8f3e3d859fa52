"""The reduction from a profiler trace to busy time, idle gaps and
kernel time."""

import pytest

from bench.harness import trace


def _trace():
    # one chip; ops on the profiler clock (seconds): busy [1.0, 1.2],
    # [1.1, 1.3] (overlapping), [2.0, 2.5]; the window is [1.0, 3.0]
    ops = [["fusion.1", 1.0, 0.2, ""], ["_paged_kernel", 1.1, 0.2,
                                        "custom-call _paged_kernel"],
           ["fusion.2", 2.0, 0.5, ""], ["early", 0.5, 0.2, ""]]
    host = [["bench.clock_sync", 0.9, 0.0], ["bench.decode", 1.0, 1.4],
            ["bench.sample", 1.35, 0.3]]
    return {"chips": {"/device:TPU:0": ops}, "host": host, "lines": {}}


def test_busy_is_the_union_of_op_intervals():
    s = trace.reduce_trace(_trace(), 1.0, 3.0)
    assert s["window_s"] == pytest.approx(2.0)
    assert s["busy_s"] == pytest.approx(0.3 + 0.5)
    assert s["chips"] == 1


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    s = trace.reduce_trace(_trace(), 1.0, 3.0,
                           host_spans=[("cpu_gemm", 2.6, 2.9)])
    gaps = dict(s["idle_gaps"])
    assert gaps["sample"] == pytest.approx(0.7)     # [1.3, 2.0]
    assert gaps["cpu_gemm"] == pytest.approx(0.5)   # [2.5, 3.0]: middle 2.75


def test_device_ops_clip_to_the_window_and_sort():
    s = trace.reduce_trace(_trace(), 1.0, 3.0)
    names = [n for n, _ in s["device_ops"]]
    assert names[0] == "fusion.2" and "early" not in names


def test_kernel_time_matches_name_or_description():
    secs, n = trace.kernel_time(_trace(), 1.0, 3.0, "_paged_kernel")
    assert (secs, n) == (pytest.approx(0.2), 1)


def test_clock_offset_from_the_sync_mark():
    assert trace.clock_offset(_trace(), 100.0) == pytest.approx(-99.1)


def test_window_only_keeps_what_overlaps():
    w = trace.window_only(_trace(), 0.15, 1.2)     # [1.05, 2.1]
    names = [op[0] for op in w["chips"]["/device:TPU:0"]]
    assert names == ["fusion.1", "_paged_kernel", "fusion.2"]
    assert w["host"][0][0] == "bench.clock_sync"



# -- a trace recorded on the chip (bench/tests/data), trimmed -------------

def _recorded():
    import json
    from pathlib import Path

    path = Path(__file__).parent / "data" / "v5e_decode_step.json"
    t = json.loads(path.read_text())
    sync = t["host"][0][1]
    return t, sync + 8.10, sync + 10.62


def _decode_pattern():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).parents[1] / "metrics" / "paged_decode_roofline.py"
    spec = importlib.util.spec_from_file_location("paged_decode_roofline",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.PATTERN


def test_recorded_busy_and_idle_gaps_fill_the_window():
    t, w0, w1 = _recorded()
    s = trace.reduce_trace(t, w0, w1)
    assert s["chips"] == 1
    assert 0 < s["busy_s"] < 0.1 * s["window_s"]
    idle = sum(v for _, v in s["idle_gaps"])
    assert s["busy_s"] + idle == pytest.approx(s["window_s"], rel=1e-9)
    labels = [k for k, _ in s["idle_gaps"]]
    assert labels[:2] == ["decode", "prefill"]


def test_recorded_paged_decode_calls_are_one_per_layer():
    t, w0, w1 = _recorded()
    secs, n = trace.kernel_time(t, w0, w1, _decode_pattern())
    assert n == 4                      # one 16-row decode step, 4 layers
    _, n_all = trace.kernel_time(t, w0, w1, r"^%_paged")
    assert n_all == 8                  # and one prefill chunk's calls


def test_recorded_paged_decode_time_exceeds_its_least_time():
    """At the longest sequences the mix allows (320 tokens in every one
    of the 16 rows) the kernel's least time stays under its recorded
    time: the roofline share cannot pass 100%."""
    from bench.harness import flops
    from bench.harness.spec import Bench

    t, w0, w1 = _recorded()
    secs, n = trace.kernel_time(t, w0, w1, _decode_pattern())
    b = Bench()
    c = b.arch("opt").counts(b.config("opt-13b-fp-offload"))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    lens = [320] * 16
    least = n * flops.roofline_seconds(flops.paged_decode_flops(c, lens),
                                       flops.paged_decode_bytes(c, lens),
                                       peaks)
    assert 0 < least < secs


def test_recorded_breakdown_names_are_short():
    t, w0, w1 = _recorded()
    s = trace.reduce_trace(t, w0, w1)
    assert s["device_ops"][0][0] == "%_paged.1 = f32[640,1,128]"
    assert all(len(k) < 120 for k, _ in s["device_ops"])


def test_short_name_stops_at_the_layout():
    name = ("%_paged.1 = f32[640,1,128]{2,1,0:T(1,128)} custom-call("
            "s32[16]{0:T(128)} %kv_len.1)")
    assert trace.short_name(name) == "%_paged.1 = f32[640,1,128]"
    assert trace.short_name("fusion.2") == "fusion.2"
