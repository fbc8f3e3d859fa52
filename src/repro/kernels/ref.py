"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors one kernel's contract exactly; kernel tests sweep
shapes/dtypes and ``assert_allclose`` kernel(interpret=True) against these.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _act(y, activation: Optional[str]):
    if activation in (None, "none"):
        return y
    if activation == "relu":
        return jnp.maximum(y, 0.0)
    if activation == "relu2":
        r = jnp.maximum(y, 0.0)
        return r * r
    if activation == "gelu":
        return jax.nn.gelu(y)
    if activation == "silu":
        return y * jax.nn.sigmoid(y)
    raise ValueError(activation)


def matmul(x, w, bias=None, *, activation=None):
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return _act(y, activation).astype(x.dtype)


def gated_matmul(x, w_gate, w_up, *, activation="silu"):
    g = jnp.dot(x.astype(jnp.float32), w_gate.astype(jnp.float32))
    u = jnp.dot(x.astype(jnp.float32), w_up.astype(jnp.float32))
    return (_act(g, activation) * u).astype(x.dtype)


def q8_matmul(x, q, scale):
    y = jnp.dot(x.astype(jnp.float32), q.astype(jnp.float32))
    return (y * scale.astype(jnp.float32)[None, :]).astype(x.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None):
    """q (B,Hq,Sq,D); k/v (B,Hkv,Skv,D)."""
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, sq, d).astype(jnp.float32)
    s = jnp.einsum("bkgsd,bktd->bkgst", qf, k.astype(jnp.float32))
    s = s / math.sqrt(d)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    ok = jnp.ones((sq, skv), bool)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    s = jnp.where(ok[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,bktd->bkgsd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, sq, d).astype(q.dtype)


def decode_attention(q, k, v, kv_len, *, softcap=None):
    """q (B,Hq,D); k/v (B,Hkv,S,D); kv_len (B,)."""
    b, hq, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, d).astype(jnp.float32)
    sc = jnp.einsum("bkgd,bktd->bkgt", qf, k.astype(jnp.float32))
    sc = sc / math.sqrt(d)
    if softcap is not None:
        sc = softcap * jnp.tanh(sc / softcap)
    mask = jnp.arange(s)[None, :] < kv_len[:, None]
    sc = jnp.where(mask[:, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, d).astype(q.dtype)


def gather_pages(pages, block_tables):
    """(P, H, ps, D) pages + (B, nb) tables -> contiguous (B, H, nb*ps, D).

    The materialized-copy read of a paged cache (what the Pallas kernel's
    block-table index maps avoid); also the shared gather for prefill
    attention over paged caches.
    """
    g = pages[block_tables]                    # (B, nb, H, ps, D)
    b, nb, h, ps, d = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, h, nb * ps, d)


def gather_page_scales(scales, block_tables):
    """(P, H, ps) scale pages + (B, nb) tables -> (B, H, nb*ps)."""
    g = scales[block_tables]                   # (B, nb, H, ps)
    b, nb, h, ps = g.shape
    return g.transpose(0, 2, 1, 3).reshape(b, h, nb * ps)


def paged_decode_attention(q, k_pages, v_pages, block_tables, kv_len, *,
                           k_scale=None, v_scale=None, softcap=None):
    """q (B,Hq,D); k/v_pages (P,Hkv,ps,D); block_tables (B,nb); kv_len (B,).

    Gathers physical pages into a contiguous cache, then defers to the
    dense :func:`decode_attention` oracle — positions >= kv_len are
    masked, so trash-page contents never reach the softmax.
    """
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    if k_scale is not None:
        k = k.astype(jnp.float32) \
            * gather_page_scales(k_scale, block_tables)[..., None]
        v = v.astype(jnp.float32) \
            * gather_page_scales(v_scale, block_tables)[..., None]
    out = decode_attention(q, k, v, kv_len, softcap=softcap)
    return out.astype(q.dtype)


def paged_prefill_attention(q, k_pages, v_pages, block_tables, kv_offset, *,
                            k_scale=None, v_scale=None, softcap=None,
                            window=None):
    """q (B,Hq,S,D); k/v_pages (P,Hkv,ps,D); block_tables (B,nb);
    kv_offset (B,).

    Chunk prefill over a paged cache: query row r of batch b sits at
    absolute position ``kv_offset[b] + r`` and attends causally over
    logical kv positions [0, kv_offset[b] + r].  Gathers physical pages
    into a contiguous cache and applies the masked softmax directly —
    positions above the causal diagonal (which includes everything past
    ``kv_offset + S``) never reach the softmax, so trash-page contents
    are irrelevant.
    """
    b, hq, s, d = q.shape
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    if k_scale is not None:
        k = k.astype(jnp.float32) \
            * gather_page_scales(k_scale, block_tables)[..., None]
        v = v.astype(jnp.float32) \
            * gather_page_scales(v_scale, block_tables)[..., None]
    hkv, t = k.shape[1], k.shape[2]
    g = hq // hkv
    qf = q.reshape(b, hkv, g, s, d).astype(jnp.float32)
    sc = jnp.einsum("bkgsd,bktd->bkgst", qf, k.astype(jnp.float32))
    sc = sc / math.sqrt(d)
    if softcap is not None:
        sc = softcap * jnp.tanh(sc / softcap)
    qpos = kv_offset[:, None] + jnp.arange(s)[None, :]     # (B, s)
    kpos = jnp.arange(t)
    ok = kpos[None, None, :] <= qpos[:, :, None]           # (B, s, t)
    if window is not None:
        ok &= kpos[None, None, :] > qpos[:, :, None] - window
    sc = jnp.where(ok[:, None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgst,bktd->bkgsd", p, v.astype(jnp.float32))
    return o.reshape(b, hq, s, d).astype(q.dtype)


def rmsnorm(x, scale, *, eps=1e-6, plus_one=False):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    w = scale.astype(jnp.float32)
    if plus_one:
        w = w + 1.0
    return (xf * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


def ssd_chunk(x, dt, a, b, c, *, chunk: int
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle for the intra-chunk kernel (matches kernels/ssd_chunk.py)."""
    bs, ln, h, p = x.shape
    n = b.shape[-1]
    nc = ln // chunk
    xc = x.reshape(bs, nc, chunk, h, p).astype(jnp.float32)
    dtc = dt.reshape(bs, nc, chunk, h).astype(jnp.float32)
    bc = b.reshape(bs, nc, chunk, h, n).astype(jnp.float32)
    cc = c.reshape(bs, nc, chunk, h, n).astype(jnp.float32)
    la = dtc * a
    cum = jnp.cumsum(la, axis=2)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]
    ii = jnp.arange(chunk)
    causal = ii[:, None] >= ii[None, :]
    decay = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    xdt = xc * dtc[..., None]
    cb = jnp.einsum("bnkhs,bnlhs->bnklh", cc, bc)
    y = jnp.einsum("bnklh,bnklh,bnlhp->bnkhp", cb, decay, xdt)
    tail = jnp.exp(cum[:, :, -1:, :] - cum)
    sc = jnp.einsum("bnkh,bnkhs,bnkhp->bnhps", tail, bc, xdt)
    return (y.reshape(bs, ln, h, p).astype(x.dtype), sc,
            cum.reshape(bs, ln, h))


def ssm_conv_step(conv_pool, slots, x, w, bias):
    """Reference of :func:`repro.kernels.ssm_step.conv_step`: gather the
    slots' conv windows, convolve the new input, scatter the shifted
    windows back."""
    win = conv_pool[slots].astype(jnp.float32)            # (B, K-1, C)
    full = jnp.concatenate([win, x[:, None].astype(jnp.float32)], axis=1)
    y = jnp.einsum("bkc,kc->bc", full, w.astype(jnp.float32)) + bias
    pool = conv_pool.at[slots].set(full[:, 1:].astype(conv_pool.dtype))
    return jax.nn.silu(y).astype(x.dtype), pool


def ssm_scan_step(ssm_pool, slots, x, dt, b, c, a, d):
    """Reference of :func:`repro.kernels.ssm_step.scan_step`: one SSM
    recurrence step per head on the gathered slot rows."""
    h = ssm_pool[slots]                                   # (B, H, P, N)
    dt = dt.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    decay = jnp.exp(dt * a)[..., None, None]
    hnew = h * decay + (dt[..., None] * xf)[..., None] \
        * b.astype(jnp.float32)[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", hnew, c.astype(jnp.float32)) \
        + xf * d[:, None]
    return y.astype(x.dtype), ssm_pool.at[slots].set(hnew)
