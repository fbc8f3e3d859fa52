"""A dense grouped-query decoder of the Mistral kind, for the benchmark's
own tests: RMSNorm, rotary positions, SwiGLU, query heads that share
key/value heads in groups, and an output head of its own.

It comes into a test's benchmark as files only (this module, its plain
reference ``rope_gqa_reference.py`` and a configuration naming both), the
way a new configuration joins the benchmark.

Layout (leading axis = layer for every ``layers`` leaf)::

    embed (V, d)   head (d, V)   final_norm (d,)
    layers: attn_norm wq (d, Hq hd) wk wv (d, Hkv hd) wo (Hq hd, d)
            mlp_norm w_gate w_up (d, f) w_down (f, d)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")


def shapes(c: Dict) -> Dict:
    L, d, f = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    V = c["vocab_size"]
    lay = {"attn_norm": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
           "wv": (L, d, kv), "wo": (L, q, d), "mlp_norm": (L, d),
           "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}
    return {"embed": (V, d), "head": (d, V), "final_norm": (d,),
            "layers": lay}


def make_weights(conf: Dict):
    """A jitted ``key -> weights``: projections and the head drawn with
    standard deviation ``1/sqrt(fan_in)``, norm scales around 1."""
    shp, init, dtype = shapes(conf), conf["init"], jnp.dtype(conf["dtype"])
    flat = [("embed", shp["embed"]), ("head", shp["head"]),
            ("final_norm", shp["final_norm"])]
    flat += list(shp["layers"].items())

    def make(key):
        out: Dict = {"layers": {}}
        for k, (name, s) in zip(jax.random.split(key, len(flat)), flat):
            x = jax.random.normal(k, s, jnp.float32)
            if name.endswith("norm"):
                x = 1.0 + x * init["norm_scale_std"]
            elif name == "embed":
                x = x * init["embed_std_times_sqrt_d"] / math.sqrt(s[-1])
            else:
                x = x / math.sqrt(s[-2])
            (out["layers"] if name in shp["layers"] else out)[name] = \
                x.astype(dtype)
        return out

    return jax.jit(make)


def program_config(conf: Dict):
    """The program's ``ModelConfig``, checked to be this block."""
    from repro.configs import get_config

    c = conf
    cfg = dataclasses.replace(
        get_config(c["program_arch"]), n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        max_seq=c["max_position_embeddings"], rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], tie_embeddings=c["tie_word_embeddings"],
        dtype=c["dtype"])
    want = dict(family="dense", attn_kind="gqa", pos_emb="rope",
                norm_kind="rmsnorm", mlp_kind="gated_silu", attn_bias=False,
                tie_embeddings=False, qk_norm=False, post_norm=False,
                emb_scale=False, window=None, layer_pattern=None,
                attn_softcap=None, logit_softcap=None)
    for k, v in want.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"{c['program_arch']}: {k}={getattr(cfg, k)!r}"
                             f" is not this block ({v!r})")
    return cfg


def program_params(w: Dict) -> Dict:
    """The program's stacked parameter tree; linears on the host."""
    lay = w["layers"]
    host = {k: np.asarray(jax.device_get(lay[k])) for k in ATTN + MLP}
    blocks = {"pos0": {"ln1": {"scale": lay["attn_norm"]},
                       "ln2": {"scale": lay["mlp_norm"]},
                       "attn": {k: host[k] for k in ATTN},
                       "mlp": {k: host[k] for k in MLP}}}
    return {"embed": w["embed"], "lm_head": w["head"],
            "final_norm": {"scale": w["final_norm"]}, "blocks": blocks}


def counts(conf: Dict) -> Dict[str, int]:
    """The work of one token (keys as in ``bench/configs/opt_arch.py``):
    queries and keys differ in width, so K/V bytes follow the key/value
    heads and attention FLOPs the query heads."""
    L, d, f = (conf["num_hidden_layers"], conf["hidden_size"],
               conf["intermediate_size"])
    q = conf["num_attention_heads"] * conf["head_dim"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    item = jnp.dtype(conf["dtype"]).itemsize
    return {"linear_params": L * (2 * d * q + 2 * d * kv + 3 * d * f),
            "head_params": d * conf["vocab_size"],
            "paged_layers": L,
            "kv_bytes_per_key": 2 * kv * item,
            "qo_bytes_per_row": 2 * q * item,
            "attn_flops_per_key": 4 * q}
