"""Discovery: every piece of a cell is a file found by its name."""

import json

import pytest

from bench.harness import spec
from bench.tests.conftest import ROOT, TINY, make_bench_dir


def test_the_benchmark_resolves_every_name_it_gives():
    b = spec.Bench()
    for c in b.spec["configs"]:
        conf = b.config(c["name"])
        assert (ROOT / c["file"]).is_file()
        b.reference(conf["reference"])
        arch = b.arch(conf["arch"])
        assert set(arch.counts(conf)) >= {"linear_params", "head_params"}
        for k in c["reduced"]:
            assert conf[k] != conf["published"][k]
    for w in b.spec["workloads"]:
        b.traffic(w["traffic"])
        lim = b.limits(w["name"])
        assert {"max_logit_gap", "max_logit_err"} <= set(lim)
        assert b.metric_specs(w["name"], False), w["name"]
        assert b.metric_specs(w["name"], True), w["name"]
    for m in b.spec["end_to_end"] + b.spec["per_layer"]:
        assert callable(b.metric_reader(m["name"]))
    assert b.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_new_config_mix_and_metric_need_no_code_edit(tmp_path):
    """Files and entries added beside the rest are found by name."""
    root = make_bench_dir(
        tmp_path, cells={"new.cell": ("new-config", "new-mix")},
        configs={"new-config": dict(TINY, hidden_size=96)})
    bench_dir = root / "bench"
    (bench_dir / "traffic" / "new-mix.json").write_text(
        json.dumps({"loop": "closed", "marker": 7}))
    (bench_dir / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return ctx.marker * 2\n")
    s = json.loads((root / "BENCHMARK.json").read_text())
    s["per_layer"].append({"name": "new.metric", "unit": "count",
                           "better": "lower", "source": "program_counter",
                           "layer": "scheduler", "moves": "itl_p50_ms",
                           "workloads": ["new.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    b = spec.Bench(root=root, bench_dir=bench_dir)
    assert b.config(b.cell("new.cell")["config"])["hidden_size"] == 96
    assert b.traffic(b.cell("new.cell")["traffic"])["marker"] == 7

    class Ctx:
        marker = 21

    specs = [m for m in b.metric_specs("new.cell", True)
             if m["name"] == "new.metric"]
    assert spec.read_metrics(b, specs, Ctx()) == {
        "new.metric": {"value": 42.0, "unit": "count"}}


def test_a_reader_that_finds_nothing_leaves_its_metric_out(tmp_path):
    root = make_bench_dir(tmp_path)
    (root / "bench" / "metrics" / "silent.py").write_text(
        "def read(ctx):\n    return None\n")
    b = spec.Bench(root=root, bench_dir=root / "bench")
    m = {"name": "silent", "unit": "%"}
    assert spec.read_metrics(b, [m], object()) == {}


def test_per_layer_metrics_follow_their_cells(tmp_path):
    """A per-layer metric with ``workloads`` belongs to those cells; one
    without belongs to every cell that reports the metric it moves."""
    root = make_bench_dir(tmp_path, cells={"tiny.closed": ("tiny-opt",
                                                           "tiny-closed"),
                                           "tiny.other": ("tiny-opt",
                                                          "tiny-closed")})
    s = json.loads((root / "BENCHMARK.json").read_text())
    s["per_layer"].append({"name": "everywhere", "unit": "count",
                           "better": "lower", "source": "program_counter",
                           "layer": "scheduler", "moves": "itl_p50_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    b = spec.Bench(root=root, bench_dir=root / "bench")
    listed = {m["name"] for m in b.metric_specs("tiny.closed", True)}
    other = {m["name"] for m in b.metric_specs("tiny.other", True)}
    assert {"step_mfu", "sched.decode_batch", "everywhere"} <= listed
    assert other == {"everywhere"}
    assert {m["name"] for m in b.metric_specs("tiny.other", False)} == {
        "itl_p50_ms", "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "../TPU v5 lite"])
def test_an_unknown_device_kind_is_an_error(kind):
    with pytest.raises(spec.SpecError):
        spec.Bench().peaks(kind)


@pytest.mark.parametrize("name", ["../x", "a b", "", "x/y"])
def test_a_name_outside_the_alphabet_is_refused(name):
    with pytest.raises(spec.SpecError):
        spec.Bench().traffic(name)
