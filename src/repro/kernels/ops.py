"""Dispatch layer: jit'd public kernel API.

On a TPU backend the Pallas kernels are compiled natively; on the CPU
backend (tests and rehearsals) the pure-jnp references are the compiled
path and the kernels run under ``interpret=True`` only in tests.  A run
that must be on the chip checks ``_mode() == "pallas"`` itself
(``chip_smoke.py``), since a CPU run would otherwise pass on the
references.  ``set_mode`` overrides the choice:

    repro_kernels.set_mode("pallas")      # TPU production
    repro_kernels.set_mode("ref")         # CPU/XLA fallback
    repro_kernels.set_mode("interpret")   # kernel body on CPU (tests)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import hete_matmul as _mm
from repro.kernels import paged_attention as _paged
from repro.kernels import paged_prefill as _paged_pf
from repro.kernels import q8_matmul as _q8
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_chunk as _ssd
from repro.kernels import ssm_step as _ssm_step

_MODE: Optional[str] = None


def set_mode(mode: Optional[str]) -> None:
    global _MODE
    assert mode in (None, "pallas", "ref", "interpret")
    _MODE = mode


def _mode() -> str:
    if _MODE is not None:
        return _MODE
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@functools.partial(jax.jit, static_argnames=("activation",))
def _matmul_ref(x, w, bias=None, activation=None):
    return _ref.matmul(x, w, bias, activation=activation)


def matmul(x, w, bias=None, *, activation=None, **kw):
    m = _mode()
    if m == "ref":
        return _matmul_ref(x, w, bias, activation)
    return _mm.matmul(x, w, bias, activation=activation,
                      interpret=(m == "interpret"), **kw)


def gated_matmul(x, w_gate, w_up, *, activation="silu", **kw):
    m = _mode()
    if m == "ref":
        return _ref.gated_matmul(x, w_gate, w_up, activation=activation)
    return _mm.gated_matmul(x, w_gate, w_up, activation=activation,
                            interpret=(m == "interpret"), **kw)


def q8_matmul(x, q, scale, **kw):
    m = _mode()
    if m == "ref":
        return _ref.q8_matmul(x, q, scale)
    return _q8.q8_matmul(x, q, scale, interpret=(m == "interpret"), **kw)


quantize_weights = _q8.quantize_weights


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None, **kw):
    m = _mode()
    if m == "ref":
        return _ref.flash_attention(q, k, v, causal=causal, window=window,
                                    softcap=softcap)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap,
                               interpret=(m == "interpret"), **kw)


def decode_attention(q, k, v, kv_len, *, softcap=None, **kw):
    m = _mode()
    if m == "ref":
        return _ref.decode_attention(q, k, v, kv_len, softcap=softcap)
    return _dec.decode_attention(q, k, v, kv_len, softcap=softcap,
                                 interpret=(m == "interpret"), **kw)


def paged_decode_attention(q, k_pages, v_pages, block_tables, kv_len, *,
                           k_scale=None, v_scale=None, softcap=None, **kw):
    m = _mode()
    if m == "ref":
        return _ref.paged_decode_attention(
            q, k_pages, v_pages, block_tables, kv_len,
            k_scale=k_scale, v_scale=v_scale, softcap=softcap)
    return _paged.paged_decode_attention(
        q, k_pages, v_pages, block_tables, kv_len,
        k_scale=k_scale, v_scale=v_scale, softcap=softcap,
        interpret=(m == "interpret"), **kw)


def paged_prefill_attention(q, k_pages, v_pages, block_tables, kv_offset, *,
                            k_scale=None, v_scale=None, softcap=None,
                            window=None, **kw):
    m = _mode()
    if m == "ref":
        return _ref.paged_prefill_attention(
            q, k_pages, v_pages, block_tables, kv_offset,
            k_scale=k_scale, v_scale=v_scale, softcap=softcap, window=window)
    return _paged_pf.paged_prefill_attention(
        q, k_pages, v_pages, block_tables, kv_offset,
        k_scale=k_scale, v_scale=v_scale, softcap=softcap, window=window,
        interpret=(m == "interpret"), **kw)


def rmsnorm(x, scale, *, eps=1e-6, plus_one=False, **kw):
    m = _mode()
    if m == "ref":
        return _ref.rmsnorm(x, scale, eps=eps, plus_one=plus_one)
    return _rn.rmsnorm(x, scale, eps=eps, plus_one=plus_one,
                       interpret=(m == "interpret"), **kw)


def ssd_chunk(x, dt, a, b, c, *, chunk, **kw):
    m = _mode()
    if m == "ref":
        return _ref.ssd_chunk(x, dt, a, b, c, chunk=chunk)
    return _ssd.ssd_chunk(x, dt, a, b, c, chunk=chunk,
                          interpret=(m == "interpret"), **kw)


def ssm_conv_step(conv_pool, slots, x, w, bias, **kw):
    m = _mode()
    if m == "ref":
        return _ref.ssm_conv_step(conv_pool, slots, x, w, bias)
    return _ssm_step.conv_step(conv_pool, slots, x, w, bias,
                               interpret=(m == "interpret"), **kw)


def ssm_scan_step(ssm_pool, slots, x, dt, b, c, a, d, **kw):
    m = _mode()
    if m == "ref":
        return _ref.ssm_scan_step(ssm_pool, slots, x, dt, b, c, a, d)
    return _ssm_step.scan_step(ssm_pool, slots, x, dt, b, c, a, d,
                               interpret=(m == "interpret"), **kw)
