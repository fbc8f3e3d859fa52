"""Plain float32 reference of the OPT decoder (Zhang et al. 2022).

Written from the paper's description in straightforward ``jax.numpy``:
learned absolute positions added to the token embedding, pre-layernorm
decoder blocks (multi-head causal self-attention with biases, then a ReLU
feed-forward network with biases), a final layernorm, and an output head
tied to the token embedding.  No kernels, no cache, no batching: each
sequence is run whole and every position's logits come out.  Matrix
products run at ``precision="highest"`` so float32 means float32 on a TPU.

Departures from the published model, each shared with the program under
test: positions index the table from 0 (the released checkpoints offset
them by 2); layernorm epsilon is 1e-5; there is no dropout (inference).

``control="bf16"`` gives the lower-precision control that the
correctness limits are set against: every matrix product (the linears,
attention and the head) in one bfloat16 pass — both operands rounded to
bfloat16, float32 accumulation — which is what a TPU does for float32
products at its default precision, written out so that it computes the
same on any backend.  Everything else stays in float32.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

EPS = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST


def _ln(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * scale + bias


def _einsum(spec: str, a, b, control: Optional[str]):
    if control is None:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if control != "bf16":
        raise ValueError(f"unknown control {control!r}")
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _layer(x, p, n_heads: int, control: Optional[str]):
    s, d = x.shape
    hd = d // n_heads

    def lin(h, w, b):
        return _einsum("sk,kn->sn", h, w, control) + b

    h = _ln(x, p["ln1_scale"], p["ln1_bias"])
    q = lin(h, p["wq"], p["bq"]).reshape(s, n_heads, hd)
    k = lin(h, p["wk"], p["bk"]).reshape(s, n_heads, hd)
    v = lin(h, p["wv"], p["bv"]).reshape(s, n_heads, hd)
    att = _einsum("qhd,khd->hqk", q, k, control) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jnp.where(causal[None], att, -jnp.inf)
    att = jax.nn.softmax(att, axis=-1)
    o = _einsum("hqk,khd->qhd", att, v, control).reshape(s, d)
    x = x + lin(o, p["wo"], p["bo"])
    h = _ln(x, p["ln2_scale"], p["ln2_bias"])
    h = jax.nn.relu(lin(h, p["w_in"], p["b_in"]))
    return x + lin(h, p["w_down"], p["b_down"])


def logits(w: Dict, tokens: jax.Array, *, conf: Dict,
           control: Optional[str] = None):
    """Logits (S, V) at every position of one token sequence (S,), for
    the configuration ``conf`` (the reference reads its head count)."""
    return _logits(w, tokens, n_heads=conf["num_attention_heads"],
                   control=control)


@functools.partial(jax.jit, static_argnames=("n_heads", "control"))
def _logits(w: Dict, tokens: jax.Array, *, n_heads: int,
            control: Optional[str] = None):
    s = tokens.shape[0]
    x = w["embed"][tokens] + w["pos"][jnp.arange(s)]

    def body(x, p):
        return _layer(x, p, n_heads, control), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    x = _ln(x, w["final_ln_scale"], w["final_ln_bias"])
    return _einsum("sd,vd->sv", x, w["embed"], control)
