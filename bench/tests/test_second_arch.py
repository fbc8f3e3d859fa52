"""A second architecture joins the benchmark by its files alone.

The tiny grouped-query decoder under ``data/`` (RMSNorm, rotary
positions, SwiGLU, 4 query heads over 2 key/value heads, an output head
of its own) is put beside the tiny OPT configuration in a throwaway
benchmark, as a configuration file, an architecture module and a plain
reference, and driven end to end on the CPU through
``bench.harness.cell.execute`` and the program's ``HeteGenBackend`` at
budget 0.
"""

import json
import shutil
import time
from pathlib import Path

import pytest

from bench.harness import check, spec
from bench.tests.conftest import BENCH, TINY, make_bench_dir
from bench.tests.test_harness import _alter_token

DATA = Path(__file__).parent / "data"
FILES = ("rope_gqa_arch.py", "rope_gqa_reference.py")
SEED = 2**31 + 23


@pytest.fixture(scope="module")
def bench(tmp_path_factory, jax_restored):
    gqa = json.loads((DATA / "tiny-gqa.json").read_text())
    root = make_bench_dir(
        tmp_path_factory.mktemp("bench"),
        cells={"tiny.closed": ("tiny-opt", "tiny-closed"),
               "gqa.closed": ("tiny-gqa", "tiny-closed"),
               "nowhere.closed": ("tiny-nowhere", "tiny-closed")},
        configs={"tiny-opt": TINY, "tiny-gqa": gqa,
                 "tiny-nowhere": dict(gqa, arch="nowhere")})
    for f in FILES:
        shutil.copy(DATA / f, root / "bench" / "configs")
    return spec.Bench(root=root, bench_dir=root / "bench")


def _run(bench, cell="gqa.closed", control=False):
    from bench.harness.cell import execute
    return execute(bench, cell, SEED, 3.0, False,
                   t_start=time.perf_counter(), log=lambda s: None,
                   require_tpu=False, control=control)


def test_second_architecture_is_correct_and_its_control_is_not(bench):
    out = _run(bench, control=True)
    assert out["correct"], out["checks"]
    limits = {k: v["limit"] for k, v in out["checks"].items()}
    assert not check.judge(out["control"], limits), (out["checks"],
                                                     out["control"])
    assert out["metrics"]["itl_p50_ms"]["value"] > 0


def test_second_architecture_altered_token_is_not_correct(bench,
                                                          monkeypatch):
    _alter_token(monkeypatch)
    out = _run(bench)
    assert not out["correct"], out["checks"]


def test_a_missing_architecture_module_is_named(bench):
    with pytest.raises(spec.SpecError, match=r"nowhere_arch\.py"):
        _run(bench, cell="nowhere.closed")


def test_no_harness_file_knows_the_second_architecture():
    names = ("rope_gqa", "tiny-gqa", "mistral")
    for d in ("harness", "metrics"):
        for f in (BENCH / d).glob("*.py"):
            text = f.read_text()
            assert not any(n in text for n in names), f
