"""The Nemotron-H hybrid block (NVIDIA 2025) as the benchmark runs it:
seeded weights, the program's configuration and parameter tree, and the
work each token costs.

A configuration file names this module with ``"arch": "nemotron_h"``; the
harness finds it by that name (``bench/harness/spec.py``) and calls only
the four functions below.

The layers follow the file's ``hybrid_override_pattern``, one letter a
layer: ``M`` a Mamba-2 mixer, ``*`` attention, ``-`` an MLP.  Weights are
made layer by layer from the run's seed: each is drawn on the device (one
jitted call per shape) and the linears and the embedding move to the host
at once, as an offload deployment holds them; the head and the small
per-layer vectors stay on the device, where both the program and the
reference multiply by the head.  The same call feeds the program
(:func:`program_params`, which moves the embedding to the device) and,
after the program has been freed, the plain reference
(``nemotron_h_reference``), whose layout this is::

    embed (V, d)   lm_head (d, V)   final_norm (d,)
    layers[l]: norm (d,) and
      M: in_proj (d, d_inner + conv + H)  conv_w (K, conv)  conv_b (conv,)
         A_log (H,)  D (H,)  dt_bias (H,)  gnorm (d_inner,)
         out_proj (d_inner, d)
      *: wq (d, Hq hd)  wk, wv (d, Hkv hd)  wo (Hq hd, d)
      -: w_in (d, f)  w_down (f, d)

with conv = d_inner + 2 G N channels (x, B, C).  Scales come from the
configuration's ``init`` block (projections N(0, 1/fan_in); see the file's
``assumed``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

LINEARS = ("in_proj", "out_proj", "wq", "wk", "wv", "wo", "w_in", "w_down")
MIXER = {"M": "mixer", "*": "attn", "-": "mlp"}


def _sizes(c: Dict) -> Dict[str, int]:
    h, p = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n = c["n_groups"], c["ssm_state_size"]
    din = h * p
    return {"d": c["hidden_size"], "V": c["vocab_size"],
            "f": c["intermediate_size"], "H": h, "P": p, "G": g, "N": n,
            "K": c["conv_kernel"], "din": din, "conv": din + 2 * g * n,
            "hq": c["num_attention_heads"], "hkv": c["num_key_value_heads"],
            "hd": c["attention_head_dim"]}


def layer_shapes(conf: Dict, kind: str) -> Dict[str, tuple]:
    """Leaf shapes of one layer of ``kind`` (a pattern letter)."""
    z = _sizes(conf)
    d = z["d"]
    if kind == "M":
        return {"norm": (d,), "in_proj": (d, z["din"] + z["conv"] + z["H"]),
                "conv_w": (z["K"], z["conv"]), "conv_b": (z["conv"],),
                "A_log": (z["H"],), "D": (z["H"],), "dt_bias": (z["H"],),
                "gnorm": (z["din"],), "out_proj": (z["din"], d)}
    if kind == "*":
        return {"norm": (d,), "wq": (d, z["hq"] * z["hd"]),
                "wk": (d, z["hkv"] * z["hd"]), "wv": (d, z["hkv"] * z["hd"]),
                "wo": (z["hq"] * z["hd"], d)}
    return {"norm": (d,), "w_in": (d, z["f"]), "w_down": (z["f"], d)}


@functools.partial(jax.jit, static_argnames=("shape",))
def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


@functools.partial(jax.jit, static_argnames=("shape",))
def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def _draw(key, name: str, shape: tuple, init: Dict):
    """One leaf, float32, on the device."""
    if name in LINEARS:
        return _normal(key, shape) / math.sqrt(shape[0])
    if name in ("norm", "gnorm", "final_norm"):
        return 1.0 + init["norm_scale_std"] * _normal(key, shape)
    if name == "conv_w":
        return init["conv_w_std"] * _normal(key, shape)
    if name == "conv_b":
        return init["conv_b_std"] * _normal(key, shape)
    if name == "A_log":
        return jnp.log(_uniform(key, shape, *init["A_range"]))
    if name == "dt_bias":
        lo, hi = (math.log(v) for v in init["dt_range"])
        step = jnp.exp(_uniform(key, shape, lo, hi))
        return step + jnp.log(-jnp.expm1(-step))        # softplus^-1
    if name == "D":
        return 1.0 + init["D_std"] * _normal(key, shape)
    raise KeyError(name)


def make_weights(conf: Dict):
    """A ``key -> weights`` callable for this configuration: layer by
    layer; linears and embedding on the host (numpy), the rest on the
    device."""
    z, init = _sizes(conf), conf["init"]
    pattern = conf["hybrid_override_pattern"]
    if len(pattern) != conf["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern must give every layer")
    if jnp.dtype(conf["dtype"]) != jnp.float32:
        raise ValueError("weights are made in float32 only")

    emb_std = init["embed_std_times_sqrt_d"] / math.sqrt(z["d"])

    def host(x):
        return np.asarray(jax.device_get(x))

    def make(key):
        top = jax.random.split(key, 3)
        w: Dict = {"embed": host(_normal(top[0], (z["V"], z["d"])) * emb_std),
                   "lm_head": _normal(top[1], (z["d"], z["V"])) * emb_std,
                   "final_norm": _draw(top[2], "final_norm", (z["d"],),
                                       init),
                   "layers": []}
        for l, kind in enumerate(pattern):
            lkey = jax.random.fold_in(key, 1000 + l)
            shp = layer_shapes(conf, kind)
            lay = {}
            for i, (name, s) in enumerate(shp.items()):
                x = _draw(jax.random.fold_in(lkey, i), name, s, init)
                lay[name] = host(x) if name in LINEARS else x
                del x
            w["layers"].append(lay)
        return w

    return make


def program_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registered model's block with the file's sizes, checked to be the
    Nemotron-H block."""
    from repro.configs import get_config

    base = get_config(conf["program_arch"])
    z = _sizes(conf)
    pattern = conf["hybrid_override_pattern"]
    cfg = dataclasses.replace(
        base, n_layers=len(pattern), block_pattern=pattern,
        d_model=z["d"], vocab_size=z["V"], d_ff=z["f"], n_heads=z["hq"],
        n_kv_heads=z["hkv"], head_dim=z["hd"], ssm_head_dim=z["P"],
        ssm_state=z["N"], ssm_groups=z["G"], ssm_conv=z["K"],
        ssm_expand=conf["expand"], ssm_chunk=conf["chunk_size"],
        norm_eps=conf["layer_norm_epsilon"],
        max_seq=conf["max_position_embeddings"], dtype=conf["dtype"])
    want = dict(ssm_heads=z["H"], d_inner=z["din"], mlp_kind="relu2",
                pos_emb="none", attn_kind="gqa", norm_kind="rmsnorm",
                attn_bias=False, qk_norm=False, post_norm=False,
                tie_embeddings=conf["tie_word_embeddings"], family="hybrid")
    for k, v in want.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"{conf['program_arch']}: {k}={getattr(cfg, k)!r}"
                             f" is not the Nemotron-H block's {v!r}")
    return cfg


def program_params(w: Dict) -> Dict:
    """The program's parameter tree (``repro.models.model``'s block-pattern
    layout: a list of layers, each a norm and its one mixer) from the
    benchmark's weights.  The linears stay the host arrays they are; the
    embedding moves to the device, where the program reads it beside the
    head."""
    layers = []
    for lay in w["layers"]:
        kind = "M" if "in_proj" in lay else ("*" if "wq" in lay else "-")
        mixer = {k: v for k, v in lay.items() if k != "norm"}
        layers.append({"norm": {"scale": lay["norm"]}, MIXER[kind]: mixer})
    return {"embed": jnp.asarray(w["embed"]),
            "lm_head": jnp.asarray(w["lm_head"]),
            "final_norm": {"scale": w["final_norm"]}, "layers": layers}


def counts(conf: Dict) -> Dict[str, int]:
    """The work of one token, from sizes alone (``bench/harness/flops.py``
    and the SSM metric readers read these keys):

    ``linear_params``       weights a token multiplies through all layers:
                            Mamba in_proj and out_proj, attention q, k, v,
                            o, MLP in and down
    ``head_params``         weights of the output head (untied: V d)
    ``paged_layers``        layers whose decode runs the paged kernel (the
                            attention layers)
    ``kv_bytes_per_key``    one key's K and V in one layer (8 K/V heads)
    ``qo_bytes_per_row``    one row's query and output in one layer (64
                            query heads)
    ``attn_flops_per_key``  scores and weighted sum, 2 FLOPs each per
                            query head and head dimension
    ``ssm_layers``          layers whose decode runs the state update
    ``ssm_state_bytes_per_row``  one row's conv state ((K-1) x conv
                            channels) and SSM state (H x P x N), float32,
                            read once and written once, in one layer
    """
    z = _sizes(conf)
    pattern = conf["hybrid_override_pattern"]
    item = jnp.dtype(conf["dtype"]).itemsize
    d = z["d"]
    per = {"M": d * (z["din"] + z["conv"] + z["H"]) + z["din"] * d,
           "*": 2 * d * z["hq"] * z["hd"] + 2 * d * z["hkv"] * z["hd"],
           "-": 2 * d * z["f"]}
    state = (z["K"] - 1) * z["conv"] + z["H"] * z["P"] * z["N"]
    return {"linear_params": sum(per[k] for k in pattern),
            "head_params": z["V"] * d,
            "paged_layers": pattern.count("*"),
            "kv_bytes_per_key": 2 * z["hkv"] * z["hd"] * item,
            "qo_bytes_per_row": 2 * z["hq"] * z["hd"] * item,
            "attn_flops_per_key": 4 * z["hq"] * z["hd"],
            "ssm_layers": pattern.count("M"),
            "ssm_state_bytes_per_row": 2 * 4 * state}
