"""``engine.host_gemm_gbps``: the host GEMM's weight bytes over its busy
seconds in the window's decode phase, on hand-built spans and on the
spans a traced engine records."""

from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench.harness import spec


def _sp(name, track, t0, t1, **attrs):
    return NS(name=name, track=track, t0=t0, t1=t1, attrs=attrs or None)


def _read(spans):
    ctx = NS(spans=spans, w0=0.0, w1=10.0,
             in_window=lambda t: 0.0 <= t <= 10.0)
    return spec.Bench().metric_reader("engine.host_gemm_gbps")(ctx)


def test_reads_decode_gemm_spans_in_the_window():
    """Window [0, 10]: two decode GEMMs count; a prefill GEMM, a decode
    GEMM past the close and a transfer do not."""
    spans = [
        _sp("m0", "cpu_gemm", 1.2, 1.7, bytes=4e9, phase="decode"),
        _sp("m1", "cpu_gemm", 4.2, 4.7, bytes=2e9, phase="decode"),
        _sp("m0", "cpu_gemm", 7.1, 7.4, bytes=9e9, phase="prefill"),
        _sp("m0", "cpu_gemm", 10.2, 10.5, bytes=9e9, phase="decode"),
        _sp("m0", "transfer", 2.0, 2.1, bytes=1e9, phase="decode"),
    ]
    # 6e9 bytes over 1 s of decode GEMM
    assert _read(spans) == pytest.approx(6.0)


@pytest.mark.parametrize("spans", [
    [],
    [_sp("m0", "cpu_gemm", 1.0, 2.0)],
    [_sp("m0", "cpu_gemm", 1.0, 2.0, bytes=4e9, phase="prefill")],
], ids=["no-spans", "unlabelled", "prefill-only"])
def test_reads_nothing_without_decode_gemm_spans(spans):
    assert _read(spans) is None


def test_reads_the_spans_a_traced_engine_records():
    """A decode-phase engine's ``cpu_gemm`` spans: Σ bytes / Σ busy."""
    import jax.numpy as jnp

    from repro.core import HeteGenEngine, ModulePlan
    from repro.telemetry.tracer import Tracer

    rng = np.random.default_rng(0)
    names = ["m0", "m1"]
    W = {n: rng.standard_normal((96, 256)).astype(np.float32)
         for n in names}
    tr = Tracer()
    eng = HeteGenEngine(W, [ModulePlan("m0", "g", "hetegen", 0.5),
                            ModulePlan("m1", "g", "host", 0.0)],
                        tracer=tr, trace_phase="decode")
    eng.warm_prefetch()
    x = jnp.asarray(rng.standard_normal((16, 1, 96)).astype(np.float32))
    for n in names:
        eng.linear(x, n)
    eng.close()
    gemms = tr.spans(track="cpu_gemm")
    assert [s.attrs["bytes"] for s in gemms] == [96 * 128 * 4, 96 * 256 * 4]
    ctx = NS(spans=tr.spans(), in_window=lambda t: True)
    got = spec.Bench().metric_reader("engine.host_gemm_gbps")(ctx)
    assert got == pytest.approx(
        96 * 384 * 4 / sum(s.t1 - s.t0 for s in gemms) / 1e9)
