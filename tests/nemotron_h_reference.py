"""Plain float32 reference of the Nemotron-H hybrid decoder (NVIDIA 2025,
arXiv 2504.03624; the layer equations of the released ``config.json``).

Written from the published description in straightforward ``jax.numpy``.
The layers follow ``hybrid_override_pattern``, one letter a layer, and
every layer is pre-norm with a residual, ``x + mixer(RMSNorm(x))``:

* ``M`` — a Mamba-2 mixer: ``in_proj`` gives z, x B C and dt; a causal
  depthwise conv over x B C (with bias) and SiLU; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the selective scan
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``
  (B and C shared by the heads of a group); a gated RMSNorm
  ``norm(y * silu(z))`` over groups of ``d_inner / n_groups`` channels;
  ``out_proj``;
* ``*`` — grouped-query causal softmax attention with no positional
  embedding;
* ``-`` — ``down(relu(up(x))^2)``.

A final RMSNorm and an untied output head give the logits.  No kernels,
no cache, no chunking, paging or batching: each sequence is run whole,
the scan one token at a time (``lax.scan``), and every position's logits
come out.  Matrix products run at ``precision="highest"`` so float32 means
float32 on a TPU.  Each layer is one jitted call whose weights are passed
in.  Weights held on the host move to the device on the first call and
stay there, in ``w``, for the sequences that follow (the check runs
several on one set of weights; moving them again for each would cost
more than the passes themselves); the embedding is gathered where it is
held.

Departures from the published model, each shared with the program under
test: the depth and pattern are the configuration's (a slice of the
published pattern); ``time_step_limit`` (0, inf) clamps nothing and is
left out; there is no dropout (inference).

``control="bf16"`` gives the lower-precision control that the
correctness limits are set against: every matrix product (the linears,
the scan's read-out, attention and the head) in one bfloat16 pass — both
operands rounded to bfloat16, float32 accumulation — which is what a TPU
does for float32 products at its default precision, written out so that
it computes the same on any backend.  Everything else stays in float32.

Weights ``w``: ``embed`` (V, d), ``lm_head`` (d, V), ``final_norm`` (d,)
and ``layers``, one dict a layer: ``norm`` and, by kind, ``in_proj`` (d,
d_inner + conv channels + heads), ``conv_w`` (K, conv channels),
``conv_b``, ``A_log``, ``D``, ``dt_bias`` (heads), ``gnorm`` (d_inner),
``out_proj`` (d_inner, d); ``wq``, ``wk``, ``wv``, ``wo``; ``w_in``,
``w_down``.  Linears are (in, out).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    eps: float
    heads: int          # Mamba heads
    head_dim: int       # Mamba head size P
    state: int          # N
    groups: int         # G
    q_heads: int
    kv_heads: int
    attn_dim: int


def dims(conf: Dict) -> Dims:
    return Dims(float(conf["layer_norm_epsilon"]), conf["mamba_num_heads"],
                conf["mamba_head_dim"], conf["ssm_state_size"],
                conf["n_groups"], conf["num_attention_heads"],
                conf["num_key_value_heads"], conf["attention_head_dim"])


def _mm(spec: str, a, b, control: Optional[str]):
    if control is None:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if control != "bf16":
        raise ValueError(f"unknown control {control!r}")
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


@functools.partial(jax.jit, static_argnames=("dm", "control"))
def _mamba(x, p, *, dm: Dims, control):
    s = x.shape[0]
    h_, pd, n, g = dm.heads, dm.head_dim, dm.state, dm.groups
    din = h_ * pd
    h = _rms(x, p["norm"], dm.eps)
    zxbcdt = _mm("sk,kn->sn", h, p["in_proj"], control)
    z = zxbcdt[:, :din]
    xbc = zxbcdt[:, din:2 * din + 2 * g * n]
    dt = zxbcdt[:, 2 * din + 2 * g * n:]
    k = p["conv_w"].shape[0]
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = p["conv_b"] + sum(pad[i:i + s] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :din].reshape(s, h_, pd)
    bs = xbc[:, din:din + g * n].reshape(s, g, n)
    cs = xbc[:, din + g * n:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # (s, H)
    a = -jnp.exp(p["A_log"])
    group = jnp.arange(h_) // (h_ // g)                      # head -> group

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = state * jnp.exp(dtt * a)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[group][:, None, :]
        yt = _mm("hpn,hn->hp", state, ct[group], control)
        return state, yt

    _, ys = jax.lax.scan(step, jnp.zeros((h_, pd, n), jnp.float32),
                         (xs, dt, bs, cs))
    y = (ys + p["D"][:, None] * xs).reshape(s, din)
    yz = (y * jax.nn.silu(z)).reshape(s, g, din // g)
    yz = yz * jax.lax.rsqrt(jnp.mean(jnp.square(yz), -1, keepdims=True)
                            + dm.eps)
    y = yz.reshape(s, din) * p["gnorm"]
    return x + _mm("sk,kn->sn", y, p["out_proj"], control)


@functools.partial(jax.jit, static_argnames=("dm", "control"))
def _attention(x, p, *, dm: Dims, control):
    s = x.shape[0]
    hq, hkv, hd = dm.q_heads, dm.kv_heads, dm.attn_dim
    h = _rms(x, p["norm"], dm.eps)
    q = _mm("sk,kn->sn", h, p["wq"], control).reshape(s, hq, hd)
    k = _mm("sk,kn->sn", h, p["wk"], control).reshape(s, hkv, hd)
    v = _mm("sk,kn->sn", h, p["wv"], control).reshape(s, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    att = _mm("qhd,khd->hqk", q, k, control) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal[None], att, -jnp.inf), axis=-1)
    o = _mm("hqk,khd->qhd", att, v, control).reshape(s, hq * hd)
    return x + _mm("sk,kn->sn", o, p["wo"], control)


@functools.partial(jax.jit, static_argnames=("dm", "control"))
def _mlp(x, p, *, dm: Dims, control):
    h = _rms(x, p["norm"], dm.eps)
    h = jnp.square(jax.nn.relu(_mm("sk,kn->sn", h, p["w_in"], control)))
    return x + _mm("sk,kn->sn", h, p["w_down"], control)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, norm, head, *, eps: float, control):
    return _mm("sd,dv->sv", _rms(x, norm, eps), head, control)


_LAYER = {"M": _mamba, "*": _attention, "-": _mlp}


def _to_device(w: Dict) -> None:
    """Replace, in ``w``, every host array the layers and the head
    multiply by its copy on the device."""
    for p in w["layers"]:
        for k, v in p.items():
            if isinstance(v, np.ndarray):
                p[k] = jax.device_put(v)
    if isinstance(w["lm_head"], np.ndarray):
        w["lm_head"] = jax.device_put(w["lm_head"])


def logits(w: Dict, tokens: jax.Array, *, conf: Dict,
           control: Optional[str] = None):
    """Logits (S, V) at every position of one token sequence (S,), for
    the configuration ``conf`` (its pattern and sizes).  Leaves the
    weights of ``w`` it multiplies on the device."""
    dm = dims(conf)
    _to_device(w)
    emb = w["embed"]
    with jax.default_matmul_precision("highest"):
        if isinstance(emb, np.ndarray):
            # held on the host: gather there and move only the rows
            x = jnp.asarray(emb[np.asarray(tokens)], jnp.float32)
        else:
            x = emb[jnp.asarray(tokens)].astype(jnp.float32)
        for kind, p in zip(conf["hybrid_override_pattern"], w["layers"]):
            x = _LAYER[kind](x, p, dm=dm, control=control)
        return _head(x, w["final_norm"], w["lm_head"], eps=dm.eps,
                     control=control)
