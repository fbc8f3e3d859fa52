"""The OPT block lives in ``configs/opt_arch.py`` and reads as it did when
the harness held it: the same weights from a seed, the same metric
readings from the same trace."""

import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness import spec, trace
from bench.harness.serve import LayerRecord, seed_key
from bench.tests.conftest import TINY

SEED = 2**31 + 11
# sha256 of the tiny configuration's weights from SEED, on the CPU, as the
# harness's own weights module made them before the block moved out
TINY_DIGEST = "a3e96e0855a6da7f1a7cd85233d8dcf999eb843585658a9b4c6d24093a32f1b8"
# the readers on the recorded trace with _recorded_ctx's records, as they
# read with the harness's own OPT counts before the block moved out
RECORDED = {"paged_decode_roofline": 3.5858718303074433,
            "step_mfu": 0.1396466365643381}


def _digest(w) -> str:
    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_tiny_opt_weights_are_the_same_bits():
    opt = spec.Bench().arch("opt")
    w = opt.make_weights(TINY)(seed_key(SEED))
    assert _digest(w) == TINY_DIGEST


class _RecordedCtx:
    """What the readers read, over the recorded v5e trace: one 16-row
    decode step at lengths 65-320 and one prefill chunk of 256."""

    def __init__(self, counts):
        data = Path(__file__).parent / "data"
        self.trace = json.loads((data / "v5e_decode_step.json").read_text())
        sync = self.trace["host"][0][1]
        self.w0, self.w1 = sync + 8.10, sync + 10.62
        self.peaks = spec.Bench().peaks("TPU v5 lite")
        self.summary = trace.reduce_trace(self.trace, self.w0, self.w1)
        lens = [65 + 17 * i for i in range(16)]
        self.layer = LayerRecord(
            decode=[(self.w0 + 0.1, self.w0 + 0.7, 16, lens)],
            prefill=[(self.w0 + 1.0, self.w0 + 2.0, 1, 256)])
        self.counts = counts

    def in_window(self, t):
        return self.w0 <= t <= self.w1

    def kernel_time(self, pattern):
        return trace.kernel_time(self.trace, self.w0, self.w1, pattern)


@pytest.mark.parametrize("metric", sorted(RECORDED))
def test_recorded_trace_reads_as_before_the_move(metric):
    b = spec.Bench()
    counts = b.arch("opt").counts(b.config("opt-13b-fp-offload"))
    got = b.metric_reader(metric)(_RecordedCtx(counts))
    assert got == RECORDED[metric]
