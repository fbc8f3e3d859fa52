"""The harness end to end on the CPU at a tiny size.

Each test drives a whole run — set-up, warm-up, the window through
``AsyncLLM``, the check against the plain reference — with the look for a
chip skipped.  A sound run is correct and its control is not; a run whose
timed path is broken underneath is not correct.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench.harness import check, spec
from bench.tests.conftest import LIMITS, ROOT, make_bench_dir

SEED = 2**31 + 11


@pytest.fixture(scope="module")
def bench(tmp_path_factory, jax_restored):
    root = make_bench_dir(tmp_path_factory.mktemp("bench"))
    return spec.Bench(root=root, bench_dir=root / "bench")


def _run(bench, cell="tiny.closed", control=False, seconds=3.0):
    from bench.harness.cell import execute
    return execute(bench, cell, SEED, seconds, False,
                   t_start=time.perf_counter(), log=lambda s: None,
                   require_tpu=False, control=control)


def test_sound_run_is_correct_and_its_control_is_not(bench):
    out = _run(bench, control=True)
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    prog = {k: v["value"] for k, v in out["checks"].items()}
    limits = {k: v["limit"] for k, v in out["checks"].items()}
    assert not check.judge(out["control"], limits), (prog, out["control"])
    assert out["control"]["max_logit_err"] >= 3 * prog["max_logit_err"]
    assert set(out["metrics"]) == {"output_tok_s", "itl_p50_ms",
                                   "itl_p95_ms", "setup_s"}


def test_answers_that_come_after_the_close_are_waited_for(bench,
                                                         monkeypatch):
    """A window that closes before enough requests finished: the run
    sends nothing more, lets those in flight finish, and compares them."""
    from bench.harness.cell import execute

    want = 3
    monkeypatch.setattr(bench, "limits", lambda cell: dict(
        LIMITS, finished_at_least=want, wait_s=120))
    lines = []
    out = execute(bench, "tiny.closed", SEED + 2, 0.2, False,
                  t_start=time.perf_counter(), log=lines.append,
                  require_tpu=False)
    assert out["correct"], out["checks"]
    done = [ln for ln in lines if ln.startswith("finished: ")]
    assert done and int(done[0].split()[1]) >= want, lines[-6:]
    assert out["metrics"]["output_tok_s"]["value"] > 0


def _alter_token(monkeypatch):
    import jax.numpy as jnp
    import repro.serving.batcher as batcher

    def greedy(logits, key=None):
        top = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (top + 1) % logits.shape[-1]

    monkeypatch.setattr(batcher, "greedy", greedy)


def _state_unchanged(monkeypatch):
    from repro.serving.backends import HeteGenBackend
    inner = HeteGenBackend.decode

    def decode(self, token, cache):
        _, logits = inner(self, token, cache)
        return cache, logits

    monkeypatch.setattr(HeteGenBackend, "decode", decode)


def _half_batch(monkeypatch):
    from repro.serving.backends import HeteGenBackend
    inner = HeteGenBackend.decode

    def decode(self, token, cache):
        new, logits = inner(self, token, cache)
        h = logits.shape[0] // 2
        if h:
            logits = logits.at[h:2 * h].set(logits[:h])
        return new, logits

    monkeypatch.setattr(HeteGenBackend, "decode", decode)


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(bench, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(bench)
    assert not out["correct"], out["checks"]


def test_run_refuses_a_device_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "opt13b-fp.decode-batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_control_reads_above_the_program_at_each_position():
    """The control's numbers come from its own argmax and its own top."""
    ref = np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 2.9]])
    ctl = np.array([[0.0, 1.9, 2.1], [2.0, 0.0, 3.0]])
    got = check.control_readings(ref, ctl)
    assert got["max_logit_gap"] == pytest.approx([1.0, 0.1])
    assert got["max_logit_err"] == pytest.approx([1.1, 0.1])
