"""The OPT block (Zhang et al. 2022) as the benchmark runs it: seeded
weights, the program's configuration and parameter tree, and the work
each token costs.

A configuration file names this module with ``"arch": "opt"``; the
harness finds it by that name (``bench/harness/spec.py``) and calls only
the four functions below, so nothing in the harness knows the block.

Weights are made on the device in one jitted call from the run's seed,
in the dtype they are served in.  The same call feeds the program
(converted to its parameter layout by :func:`program_params`) and, after
the program has been freed, the plain reference: the reference never
reads anything the program made.

Layout (leading axis = layer for every ``layers`` leaf)::

    embed (V, d)   pos (P, d)   final_ln_scale/bias (d,)
    layers: ln1_scale ln1_bias wq bq wk bk wv bv wo bo
            ln2_scale ln2_bias w_in b_in w_down b_down

Scales come from the configuration's ``init`` block: every projection is
drawn with standard deviation ``1/sqrt(fan_in)`` so each layer's output is
of the order of its input and the layers, not the embedding of the last
token, decide the next token; norms and biases are drawn around their
identity so their code paths are exercised.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

LINEARS = ("wq", "wk", "wv", "wo", "w_in", "w_down")


def shapes(sizes: Dict) -> Dict:
    """Leaf shapes from the configuration's sizes."""
    L, d, f = sizes["num_hidden_layers"], sizes["hidden_size"], sizes["ffn_dim"]
    V, P = sizes["vocab_size"], sizes["max_position_embeddings"]
    lay = {"ln1_scale": (L, d), "ln1_bias": (L, d),
           "ln2_scale": (L, d), "ln2_bias": (L, d),
           "wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d), "wo": (L, d, d),
           "bq": (L, d), "bk": (L, d), "bv": (L, d), "bo": (L, d),
           "w_in": (L, d, f), "b_in": (L, f), "w_down": (L, f, d),
           "b_down": (L, d)}
    return {"embed": (V, d), "pos": (P, d), "final_ln_scale": (d,),
            "final_ln_bias": (d,), "layers": lay}


def _std(name: str, shape, init: Dict) -> float:
    if name in LINEARS:
        return 1.0 / math.sqrt(shape[-2])
    if name in ("embed", "pos"):
        return init["embed_std_times_sqrt_d"] / math.sqrt(shape[-1])
    if name.endswith("scale"):
        return init["norm_scale_std"]
    if name.endswith("ln_bias") or name.startswith("ln"):
        return init["norm_bias_std"]
    return init["bias_std"]


def make_weights(conf: Dict):
    """A jitted ``key -> weights`` for this configuration."""
    shp, init, dtype = shapes(conf), conf["init"], jnp.dtype(conf["dtype"])
    flat = [("embed", shp["embed"]), ("pos", shp["pos"]),
            ("final_ln_scale", shp["final_ln_scale"]),
            ("final_ln_bias", shp["final_ln_bias"])]
    flat += [(k, v) for k, v in shp["layers"].items()]

    def make(key):
        keys = jax.random.split(key, len(flat))
        out: Dict = {"layers": {}}
        for k, (name, s) in zip(keys, flat):
            x = jax.random.normal(k, s, jnp.float32) * _std(name, s, init)
            if name.endswith("scale"):
                x = x + 1.0
            x = x.astype(dtype)
            if name in shp["layers"]:
                out["layers"][name] = x
            else:
                out[name] = x
        return out

    return jax.jit(make)


def program_config(conf: Dict):
    """The program's ``ModelConfig`` for a configuration file, checked to
    hold exactly the file's sizes and the OPT block."""
    from repro.configs import get_config

    base = get_config(conf["program_arch"])
    s = conf
    cfg = dataclasses.replace(
        base, n_layers=s["num_hidden_layers"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_attention_heads"], d_ff=s["ffn_dim"],
        vocab_size=s["vocab_size"], max_seq=s["max_position_embeddings"],
        dtype=conf["dtype"])
    want = dict(pos_emb="learned", norm_kind="layernorm", mlp_kind="relu",
                attn_bias=True, tie_embeddings=True, family="dense",
                attn_kind="gqa")
    for k, v in want.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"{conf['program_arch']}: {k}={getattr(cfg, k)!r}"
                             f" is not the OPT block ({v!r})")
    if cfg.hd * cfg.n_heads != cfg.d_model:
        raise ValueError("head size times heads must equal the hidden size")
    return cfg


def program_params(w: Dict) -> Dict:
    """The program's stacked parameter tree (``repro.models.model``
    layout, one layer per super-block) from the benchmark's weights.

    The linear weights move to the host once, as numpy arrays, the way an
    offload deployment loads them; norms, biases, embeddings and
    positions stay on the device.  The caller drops ``w`` afterwards, so
    no full-model copy stays in device memory."""
    lay = w["layers"]
    host = {k: np.asarray(jax.device_get(lay[k])) for k in LINEARS}
    blocks = {"pos0": {
        "ln1": {"scale": lay["ln1_scale"], "bias": lay["ln1_bias"]},
        "ln2": {"scale": lay["ln2_scale"], "bias": lay["ln2_bias"]},
        "attn": {"wq": host["wq"], "wk": host["wk"], "wv": host["wv"],
                 "wo": host["wo"], "bq": lay["bq"], "bk": lay["bk"],
                 "bv": lay["bv"], "bo": lay["bo"]},
        "mlp": {"w_in": host["w_in"], "w_down": host["w_down"],
                "b_in": lay["b_in"], "b_down": lay["b_down"]},
    }}
    return {"embed": w["embed"], "pos": w["pos"],
            "final_norm": {"scale": w["final_ln_scale"],
                           "bias": w["final_ln_bias"]},
            "blocks": blocks}


def counts(conf: Dict) -> Dict[str, int]:
    """The work of one token, from sizes alone (``bench/harness/flops.py``
    reads these keys):

    ``linear_params``       weights a token multiplies through all layers:
                            q, k, v, o (4 d^2) and in, down (2 d f) each
    ``head_params``         weights of the output head (tied: V d)
    ``paged_layers``        layers whose decode runs the paged kernel
    ``kv_bytes_per_key``    one key's K and V in one layer (40 heads of
                            128 = d: multi-head attention)
    ``qo_bytes_per_row``    one row's query and output in one layer
    ``attn_flops_per_key``  scores and weighted sum, 2 FLOPs each per
                            head dimension
    """
    L, d, f = conf["num_hidden_layers"], conf["hidden_size"], conf["ffn_dim"]
    item = jnp.dtype(conf["dtype"]).itemsize
    return {"linear_params": L * (4 * d * d + 2 * d * f),
            "head_params": conf["vocab_size"] * d,
            "paged_layers": L,
            "kv_bytes_per_key": 2 * d * item,
            "qo_bytes_per_row": 2 * d * item,
            "attn_flops_per_key": 4 * d}
