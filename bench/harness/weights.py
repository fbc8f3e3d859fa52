"""Seeded weights for an OPT-shaped configuration, made on the device.

The benchmark makes its weights itself, in one jitted call from the run's
seed, in the dtype they are served in.  The same call feeds the program
(converted to the program's parameter layout by :func:`program_params`)
and, after the program has been freed, the plain reference: the
reference never reads anything the program made.

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

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

LINEARS = ("wq", "wk", "wv", "wo", "w_in", "w_down")
BIASES = {"wq": "bq", "wk": "bk", "wv": "bv", "wo": "bo",
          "w_in": "b_in", "w_down": "b_down"}


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


def make_fn(sizes: Dict, init: Dict, dtype=jnp.float32):
    """A jitted ``key -> weights`` for these sizes."""
    shp = shapes(sizes)
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


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed))


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
