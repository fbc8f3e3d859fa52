"""Plain float32 reference of a dense grouped-query decoder of the Mistral
kind, for the benchmark's own tests.

Straightforward ``jax.numpy``: token embedding, pre-norm blocks (RMSNorm;
causal self-attention whose query heads share key/value heads in groups
of ``Hq / Hkv``, with rotary positions on queries and keys that turn the
pair (i, i + hd/2) of each head by ``position * theta^(-2i/hd)``; RMSNorm;
a SwiGLU feed-forward network ``(silu(x Wg) * (x Wu)) Wd``), a final
RMSNorm and an output head of its own.  No kernels, no cache, no
batching; products at ``precision="highest"``.

``control="bf16"``: every matrix product in one bfloat16 pass (operands
rounded to bfloat16, float32 accumulation), the rest in float32.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _einsum(spec: str, a, b, control: Optional[str]):
    if control is None:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if control != "bf16":
        raise ValueError(f"unknown control {control!r}")
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """x (S, H, hd): rotary positions 0..S-1."""
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _layer(x, p, n_heads, n_kv, hd, theta, eps, control):
    s = x.shape[0]
    h = _rms(x, p["attn_norm"], eps)
    q = _rotate(_einsum("sd,dn->sn", h, p["wq"], control)
                .reshape(s, n_heads, hd), theta)
    k = _rotate(_einsum("sd,dn->sn", h, p["wk"], control)
                .reshape(s, n_kv, hd), theta)
    v = _einsum("sd,dn->sn", h, p["wv"], control).reshape(s, n_kv, hd)
    group = n_heads // n_kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    att = _einsum("qhd,khd->hqk", q, k, control) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal[None], att, -jnp.inf), axis=-1)
    o = _einsum("hqk,khd->qhd", att, v, control).reshape(s, n_heads * hd)
    x = x + _einsum("sn,nd->sd", o, p["wo"], control)
    h = _rms(x, p["mlp_norm"], eps)
    g = jax.nn.silu(_einsum("sd,df->sf", h, p["w_gate"], control))
    u = _einsum("sd,df->sf", h, p["w_up"], control)
    return x + _einsum("sf,fd->sd", g * u, p["w_down"], control)


def logits(w: Dict, tokens: jax.Array, *, conf: Dict,
           control: Optional[str] = None):
    """Logits (S, V) at every position of one token sequence (S,)."""
    return _logits(w, tokens, n_heads=conf["num_attention_heads"],
                   n_kv=conf["num_key_value_heads"], hd=conf["head_dim"],
                   theta=float(conf["rope_theta"]),
                   eps=float(conf["rms_norm_eps"]), control=control)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "hd",
                                             "theta", "eps", "control"))
def _logits(w, tokens, *, n_heads, n_kv, hd, theta, eps, control):
    def body(x, p):
        return _layer(x, p, n_heads, n_kv, hd, theta, eps, control), None

    x, _ = jax.lax.scan(body, w["embed"][tokens], w["layers"])
    x = _rms(x, w["final_norm"], eps)
    return _einsum("sd,dv->sv", x, w["head"], control)
