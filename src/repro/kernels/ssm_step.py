"""Mamba-2 decode: one token's state update, in place in per-slot pools.

A hybrid model's recurrent state lives in two pools with one row per
serving slot (:mod:`repro.serving.kv_cache`): the causal conv's last
``K - 1`` inputs, ``conv_pool`` (S, K-1, C), and the SSM state,
``ssm_pool`` (S, H, P, N).  A decode step touches only the rows of the
slots in its batch, named by ``slots`` (a scalar-prefetch operand, so the
BlockSpec index maps turn batch row ``b`` into pool row ``slots[b]``
before the body runs) — the pools are never gathered into a batch-shaped
copy.  Each pool is aliased from input to output: the kernel reads a
row's state once and writes it back once, which is the whole of its HBM
traffic beside the (small) token inputs.

Two kernels, named so a device profile finds them (``ssm_state_conv``,
``ssm_state_scan``):

* conv: y = silu(sum_k w[k] * window[k] + bias) over the row's last
  ``K - 1`` inputs and the new one, and the window shifts by one;
* scan: h' = exp(dt * A) h + dt * x B^T and y = h' C + D x per head,
  with B and C already expanded from groups to heads by the caller.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the scan kernel's state block (heads x P x N f32) stays under this many
# bytes, so input and output blocks, double-buffered, fit scoped VMEM
STATE_BLOCK_BYTES = 1 << 20


def _conv_kernel(slots_ref, win_ref, x_ref, w_ref, b_ref, y_ref, win_out):
    del slots_ref
    k1 = win_ref.shape[1]
    win = win_ref[0].astype(jnp.float32)                 # (K-1, C)
    x = x_ref[0].astype(jnp.float32)                     # (1, C)
    w = w_ref[...].astype(jnp.float32)                   # (K, C)
    acc = b_ref[...].astype(jnp.float32) + x * w[k1:k1 + 1]
    for k in range(k1):
        acc = acc + win[k:k + 1] * w[k:k + 1]
    y_ref[0] = jax.nn.silu(acc).astype(y_ref.dtype)
    if k1 > 1:
        win_out[0, :k1 - 1] = win[1:].astype(win_out.dtype)
    win_out[0, k1 - 1:] = x.astype(win_out.dtype)


def conv_step(conv_pool: jax.Array, slots: jax.Array, x: jax.Array,
              w: jax.Array, bias: jax.Array, *, interpret: bool = False):
    """conv_pool (S, K-1, C); slots (B,) int32; x (B, C); w (K, C);
    bias (C,) -> (silu(conv) (B, C), conv_pool updated in place)."""
    s, k1, c = conv_pool.shape
    b = x.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, k1, c), lambda i, sl: (sl[i], 0, 0)),
            pl.BlockSpec((1, 1, c), lambda i, sl: (i, 0, 0)),
            pl.BlockSpec((k1 + 1, c), lambda i, sl: (0, 0)),
            pl.BlockSpec((1, c), lambda i, sl: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, c), lambda i, sl: (i, 0, 0)),
            pl.BlockSpec((1, k1, c), lambda i, sl: (sl[i], 0, 0)),
        ],
    )
    y, pool = pl.pallas_call(
        _conv_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, 1, c), x.dtype),
                   jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype)],
        input_output_aliases={1: 1},        # conv_pool -> its update
        interpret=interpret,
        name="ssm_state_conv",
    )(slots.astype(jnp.int32), conv_pool, x[:, None], w,
      bias.reshape(1, c))
    return y[:, 0], pool


def _scan_kernel(slots_ref, h_ref, x_ref, dt_ref, b_ref, c_ref, a_ref,
                 d_ref, y_ref, h_out):
    del slots_ref
    h = h_ref[0]                                         # (hb, P, N)
    hb, p = x_ref.shape[1], x_ref.shape[2]
    x = x_ref[0].astype(jnp.float32).reshape(hb, p, 1)   # P to sublanes
    dt = dt_ref[0].astype(jnp.float32)                   # (hb, 1, 1)
    bm = b_ref[0].astype(jnp.float32)                    # (hb, 1, N)
    cm = c_ref[0].astype(jnp.float32)                    # (hb, 1, N)
    decay = jnp.exp(dt * a_ref[...])                     # (hb, 1, 1)
    hnew = h * decay + (dt * x) * bm
    h_out[0] = hnew.astype(h_out.dtype)
    y = jnp.sum(hnew * cm, axis=-1, keepdims=True) + d_ref[...] * x
    y_ref[0] = y.reshape(hb, p).astype(y_ref.dtype)


def head_block(h: int, p: int, n: int) -> int:
    """Heads per grid step: the largest divisor of ``h`` whose f32 state
    block stays within :data:`STATE_BLOCK_BYTES`."""
    per_head = p * n * 4
    best = 1
    for hb in range(1, h + 1):
        if h % hb == 0 and hb * per_head <= STATE_BLOCK_BYTES:
            best = hb
    return best


def scan_step(ssm_pool: jax.Array, slots: jax.Array, x: jax.Array,
              dt: jax.Array, b: jax.Array, c: jax.Array, a: jax.Array,
              d: jax.Array, *, interpret: bool = False):
    """ssm_pool (S, H, P, N) f32; slots (B,) int32; x (B, H, P); dt
    (B, H) (after softplus); b, c (B, H, N) per head; a, d (H,) ->
    (y (B, H, P) in x's dtype, ssm_pool updated in place)."""
    _, h, p, n = ssm_pool.shape
    bs = x.shape[0]
    hb = head_block(h, p, n)
    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bs, h // hb),
        in_specs=[
            pl.BlockSpec((1, hb, p, n), lambda i, j, sl: (sl[i], j, 0, 0)),
            pl.BlockSpec((1, hb, p), lambda i, j, sl: (i, j, 0)),
            pl.BlockSpec((1, hb, 1, 1), lambda i, j, sl: (i, j, 0, 0)),
            pl.BlockSpec((1, hb, 1, n), lambda i, j, sl: (i, j, 0, 0)),
            pl.BlockSpec((1, hb, 1, n), lambda i, j, sl: (i, j, 0, 0)),
            pl.BlockSpec((hb, 1, 1), lambda i, j, sl: (j, 0, 0)),
            pl.BlockSpec((hb, 1, 1), lambda i, j, sl: (j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, p), lambda i, j, sl: (i, j, 0)),
            pl.BlockSpec((1, hb, p, n), lambda i, j, sl: (sl[i], j, 0, 0)),
        ],
    )
    y, pool = pl.pallas_call(
        _scan_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bs, h, p), x.dtype),
                   jax.ShapeDtypeStruct(ssm_pool.shape, ssm_pool.dtype)],
        input_output_aliases={1: 1},        # ssm_pool -> its update
        interpret=interpret,
        name="ssm_state_scan",
    )(slots.astype(jnp.int32), ssm_pool, x,
      dt.astype(f32)[..., None, None], b[:, :, None], c[:, :, None],
      a.astype(f32).reshape(h, 1, 1), d.astype(f32).reshape(h, 1, 1))
    return y, pool
