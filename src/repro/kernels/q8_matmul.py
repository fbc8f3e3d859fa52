"""Int8-weight matmul with per-column scales — quantized weight streaming.

HeteGen is link-bound, so streaming weights as int8 + fp32 per-column
scales cuts the PCIe/DMA bytes (2-byte bf16 -> 1-byte int8 + 4/N scale;
4-byte fp32 -> ~1/4), shifting the alpha equilibrium toward the device:
alpha* ~= T'cpu / (T'cpu + r * T'com) with r the wire ratio
(docs/ANALYSIS.md).  This is the live serving path, not an experiment:
:class:`repro.core.engine.HeteGenEngine` built with ``wstream="q8"``
quantizes each offloaded column shard once at load
(:func:`quantize_weights`), stages the ``(q, scale)`` pair through
:class:`repro.core.param_manager.AsyncParamManager`'s pinned rings (sized
to the *compressed* bytes), DMAs the pair, and computes the device share
with this kernel — the dequant happens inside the matmul, so no fp copy
of a streamed weight ever exists in HBM.  The policy layer prices the
compressed link through :attr:`repro.core.policy.LinearSpec.wire_bytes`.

Accumulates x_block @ q_block in fp32 and applies the per-column scale on
the final K step.  (Per-column — not per-tile — scales keep the epilogue a
single multiply.)
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def quantize_weights(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-output-column symmetric int8 quantization."""
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def quantize_weights_np(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side mirror of :func:`quantize_weights` (same wire format).

    The offload engine quantizes shards at load time on the host; this
    numpy twin avoids a device round-trip there.  Bit-identical to the
    jax version (tests/test_wstream.py pins them equal).
    """
    w32 = np.asarray(w, dtype=np.float32)
    scale = np.max(np.abs(w32), axis=0) / np.float32(127.0) \
        + np.float32(1e-12)
    q = np.clip(np.round(w32 / scale), -127, 127).astype(np.int8)
    return q, scale.astype(np.float32)


def _q8_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequantize the weight tile in VMEM; MXU consumes fp32/bf16
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            q_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def q8_matmul(x: jax.Array, q: jax.Array, scale: jax.Array, *,
              block_m: int = 128, block_n: int = 128, block_k: int = 128,
              interpret: bool = False) -> jax.Array:
    """x (M, K) fp  @  dequant(q (K, N) int8, scale (N,)) -> (M, N) fp.

    Any M: rows are padded up to a multiple of the row block (decode runs
    at M = batch, prefill at M = batch * prompt) and sliced off after.
    The scale travels as a (1, N) row with (1, bn) blocks — Mosaic has no
    layout for a 1-D block of a 1-D operand.
    """
    m, k = x.shape
    k2, n = q.shape
    assert k == k2 and scale.shape == (n,)
    bm = min(block_m, _round_up(m, 8))
    bn, bk = min(block_n, n), min(block_k, k)
    assert n % bn == 0 and k % bk == 0
    m_pad = _round_up(m, bm)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    n_k = k // bk
    kernel = functools.partial(_q8_kernel, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(m_pad // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((1, bn), lambda i, j, s: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, q, scale.reshape(1, n))
    return out[:m] if m_pad != m else out
