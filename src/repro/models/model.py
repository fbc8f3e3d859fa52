"""Model assembly: init / train-forward / prefill / decode for all families.

Families:

    dense | moe      decoder-only LM, scanned over super-blocks (a super-
                     block is one period of the layer pattern: e.g. gemma2's
                     (local, global) pair, maverick's (dense, moe) pair)
    ssm              Mamba2 trunk (attention-free)
    hybrid           zamba2: Mamba2 trunk + one shared attention block
                     (invoked every k layers with per-site LoRA); or, with
                     ``block_pattern`` (nemotron-h), one mixer per layer —
                     Mamba2, attention or MLP — served only through the
                     backend seam (``backend_prefill``) on a paged cache
    encdec           whisper: stub-frontend encoder + causal decoder with
                     cross attention
    vlm              llava: dense backbone whose prefill consumes
                     precomputed patch embeddings

Parameters are plain nested dicts; per-super-block leaves are stacked on a
leading axis and the trunk runs under ``lax.scan`` (keeps HLO size and
compile time independent of depth).  The KV / SSM cache is a dict pytree
carried through the same scan.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.shardings import NO_RULES, ShardingRules
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.config import ModelConfig


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _init_norm(cfg, key, d) -> Dict:
    p = {"scale": jnp.ones((d,), _dtype(cfg))}
    if cfg.norm_kind == "layernorm":
        p["bias"] = jnp.zeros((d,), _dtype(cfg))
    if cfg.post_norm:                      # gemma (1+w) rmsnorm: init w=0
        p["scale"] = jnp.zeros((d,), _dtype(cfg))
    return p


def _dense(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _init_attn(cfg, key, d_in: Optional[int] = None,
               d_out: Optional[int] = None) -> Dict:
    d = d_in or cfg.d_model
    do = d_out or cfg.d_model
    hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 8)
    dt = _dtype(cfg)
    p = {
        "wq": _dense(ks[0], (d, hq * hd), dt),
        "wk": _dense(ks[1], (d, hkv * hd), dt),
        "wv": _dense(ks[2], (d, hkv * hd), dt),
        "wo": _dense(ks[3], (hq * hd, do), dt),
    }
    if cfg.attn_bias:
        p.update(bq=jnp.zeros((hq * hd,), dt), bk=jnp.zeros((hkv * hd,), dt),
                 bv=jnp.zeros((hkv * hd,), dt),
                 bo=jnp.zeros((do,), dt))
    if cfg.qk_norm:
        p.update(q_norm=jnp.ones((hd,), dt), k_norm=jnp.ones((hd,), dt))
    return p


def _init_mla(cfg, key) -> Dict:
    d, dt = cfg.d_model, _dtype(cfg)
    h = cfg.n_heads
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": _dense(ks[0], (d, r_q), dt),
        "q_a_norm": jnp.ones((r_q,), dt),
        "wq_b": _dense(ks[1], (r_q, h * (dn + dr)), dt),
        "wkv_a": _dense(ks[2], (d, r_kv + dr), dt),
        "kv_a_norm": jnp.ones((r_kv,), dt),
        "wk_b": _dense(ks[3], (r_kv, h * dn), dt),
        "wv_b": _dense(ks[4], (r_kv, h * dv), dt),
        "wo": _dense(ks[5], (h * dv, d), dt),
    }


def _init_mlp(cfg, key, d_in: Optional[int] = None,
              d_out: Optional[int] = None) -> Dict:
    d = d_in or cfg.d_model
    do = d_out or cfg.d_model
    f, dt = cfg.d_ff, _dtype(cfg)
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind.startswith("gated"):
        return {"w_gate": _dense(ks[0], (d, f), dt),
                "w_up": _dense(ks[1], (d, f), dt),
                "w_down": _dense(ks[2], (f, do), dt)}
    p = {"w_in": _dense(ks[0], (d, f), dt),
         "w_down": _dense(ks[1], (f, do), dt)}
    if cfg.attn_bias:
        p.update(b_in=jnp.zeros((f,), dt), b_down=jnp.zeros((do,), dt))
    return p


def _init_moe(cfg, key) -> Dict:
    d, f, e, dt = cfg.d_model, cfg.d_ff, cfg.n_experts, _dtype(cfg)
    ks = jax.random.split(key, 7)
    p = {"router": _dense(ks[0], (d, e), jnp.float32)}
    if cfg.mlp_kind.startswith("gated"):
        p.update(we_gate=_dense(ks[1], (e, d, f), dt),
                 we_up=_dense(ks[2], (e, d, f), dt),
                 we_down=_dense(ks[3], (e, f, d), dt))
    else:
        p.update(we_in=_dense(ks[1], (e, d, f), dt),
                 we_down=_dense(ks[3], (e, f, d), dt))
    if cfg.shared_expert:
        p.update(ws_gate=_dense(ks[4], (d, f), dt),
                 ws_up=_dense(ks[5], (d, f), dt),
                 ws_down=_dense(ks[6], (f, d), dt))
    return p


def _init_mamba(cfg, key) -> Dict:
    """Mamba2 block.  Projections are kept separate (w_z / w_x / w_bc /
    w_dt) rather than one fused in_proj so tensor parallelism can shard
    z/x/dt on heads and keep the small B/C projection replicated — a fused
    output dim cannot be sharded without resharding at the split points
    (DESIGN.md §4)."""
    d, dt = cfg.d_model, _dtype(cfg)
    din, h = cfg.d_inner, cfg.ssm_heads
    gn = cfg.ssm_groups * cfg.ssm_state
    ks = jax.random.split(key, 8)
    return {
        "w_z": _dense(ks[0], (d, din), dt),
        "w_x": _dense(ks[1], (d, din), dt),
        "w_bc": _dense(ks[2], (d, 2 * gn), dt),
        "w_dt": _dense(ks[3], (d, h), dt),
        "conv_x_w": _dense(ks[4], (cfg.ssm_conv, din), dt, scale=0.2),
        "conv_x_b": jnp.zeros((din,), dt),
        "conv_bc_w": _dense(ks[5], (cfg.ssm_conv, 2 * gn), dt, scale=0.2),
        "conv_bc_b": jnp.zeros((2 * gn,), dt),
        "A_log": jnp.zeros((h,), jnp.float32),          # A = -1
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.full((h,), -1.0, jnp.float32),
        "gnorm": jnp.ones((din,), dt),
        "out_proj": _dense(ks[6], (din, d), dt),
        "ln": _init_norm(cfg, ks[7], d),
    }


def _init_pattern_mamba(cfg, key) -> Dict:
    """A block-pattern Mamba2 mixer (nemotron-h layout): one fused
    ``in_proj`` emitting [z | x B C | dt], a depthwise conv over the x B C
    channels with bias, per-head A/D/dt bias, and a gated RMSNorm scale.
    A, D and the dt bias are drawn around the published initialization
    (A in [-16, -1], dt in [1e-3, 1e-1] after softplus) so every term of
    the recurrence is exercised."""
    d, dt = cfg.d_model, _dtype(cfg)
    din, h = cfg.d_inner, cfg.ssm_heads
    conv_dim = cfg.ssm_conv_dim
    ks = jax.random.split(key, 8)
    step = jnp.exp(jax.random.uniform(ks[3], (h,), jnp.float32,
                                      math.log(1e-3), math.log(1e-1)))
    return {
        "in_proj": _dense(ks[0], (d, din + conv_dim + h), dt),
        "conv_w": _dense(ks[1], (cfg.ssm_conv, conv_dim), dt, scale=0.5),
        "conv_b": _dense(ks[2], (conv_dim,), dt, scale=0.1),
        "A_log": jnp.log(jax.random.uniform(ks[4], (h,), jnp.float32,
                                            1.0, 16.0)),
        "D": 1.0 + 0.1 * jax.random.normal(ks[5], (h,), jnp.float32),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),   # softplus^-1
        "gnorm": (1.0 + 0.1 * jax.random.normal(ks[6], (din,))).astype(dt),
        "out_proj": _dense(ks[7], (din, d), dt),
    }


def _init_pattern_layer(cfg, key, kind: str) -> Dict:
    """One block-pattern layer: a pre-norm and its one mixer."""
    ks = jax.random.split(key, 2)
    p: Dict = {"norm": _init_norm(cfg, ks[0], cfg.d_model)}
    if kind == "mamba":
        p["mixer"] = _init_pattern_mamba(cfg, ks[1])
    elif kind == "attn":
        p["attn"] = _init_attn(cfg, ks[1])
    else:
        p["mlp"] = _init_mlp(cfg, ks[1])
    return p


def _init_block(cfg, key, kind: str) -> Dict:
    """One layer of a given kind."""
    ks = jax.random.split(key, 6)
    if kind == "mamba":
        return _init_mamba(cfg, ks[0])
    p: Dict = {"ln1": _init_norm(cfg, ks[0], cfg.d_model),
               "ln2": _init_norm(cfg, ks[1], cfg.d_model)}
    if cfg.post_norm:
        p["ln1_post"] = _init_norm(cfg, ks[2], cfg.d_model)
        p["ln2_post"] = _init_norm(cfg, ks[3], cfg.d_model)
    if cfg.attn_kind == "mla":
        p["attn"] = _init_mla(cfg, ks[4])
    else:
        p["attn"] = _init_attn(cfg, ks[4])
    if kind == "moe":
        p["moe"] = _init_moe(cfg, ks[5])
    else:
        p["mlp"] = _init_mlp(cfg, ks[5])
    return p


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict:
    dt = _dtype(cfg)
    keys = jax.random.split(key, 16)
    params: Dict = {
        "embed": _dense(keys[0], (cfg.vocab_size, cfg.d_model), dt, scale=1.0),
        "final_norm": _init_norm(cfg, keys[1], cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(keys[2], (cfg.d_model, cfg.vocab_size), dt)
    if cfg.pos_emb == "learned":
        params["pos"] = _dense(keys[3], (cfg.max_seq, cfg.d_model), dt,
                               scale=0.02)

    kinds = cfg.layer_kinds()
    if cfg.block_pattern:
        # layers differ in kind, so they are a list, not a scanned stack
        lkeys = jax.random.split(keys[4], cfg.n_layers)
        params["layers"] = [_init_pattern_layer(cfg, k, kind)
                            for k, kind in zip(lkeys, kinds)]
        return params
    if cfg.family in ("ssm", "hybrid"):
        period = cfg.shared_attn_period or cfg.n_layers
        n_groups = cfg.n_layers // period
        tail = cfg.n_layers - n_groups * period
        lkeys = jax.random.split(keys[4], cfg.n_layers)
        blocks = [_init_mamba(cfg, k) for k in lkeys]
        trunk = [_stack(blocks[g * period:(g + 1) * period])
                 for g in range(n_groups)]
        params["blocks"] = _stack(trunk) if n_groups > 1 else \
            jax.tree.map(lambda x: x[None], trunk[0])
        if tail:
            params["tail"] = _stack(blocks[n_groups * period:])
        if cfg.family == "hybrid":
            d2 = 2 * cfg.d_model
            sk = jax.random.split(keys[5], 8)
            shared = {"ln1": _init_norm(cfg, sk[0], d2),
                      "ln2": _init_norm(cfg, sk[1], d2),
                      "attn": _init_attn(cfg, sk[2], d_in=d2, d_out=d2),
                      "mlp": _init_mlp(cfg, sk[3], d_in=d2, d_out=d2)}
            # shared block emits d2; project back to d_model
            shared["proj"] = _dense(sk[4], (d2, cfg.d_model), dt)
            params["shared"] = shared
            n_sites = len(cfg.shared_attn_sites())
            r = cfg.shared_lora_rank
            if r:
                params["shared_lora"] = {
                    "a": _dense(sk[5], (n_sites, d2, r), dt, scale=0.02),
                    "b": jnp.zeros((n_sites, r, cfg.n_heads * cfg.hd), dt),
                }
        return params

    if cfg.family == "encdec":
        ek = jax.random.split(keys[6], cfg.encoder_layers)
        params["enc_blocks"] = _stack([_init_block(cfg, k, "dense")
                                       for k in ek])
        params["enc_pos"] = _dense(keys[7], (cfg.encoder_seq, cfg.d_model),
                                   dt, scale=0.02)
        params["enc_final_norm"] = _init_norm(cfg, keys[8], cfg.d_model)
        ck = jax.random.split(keys[9], cfg.n_layers)
        params["cross"] = _stack([
            {"attn": _init_attn(cfg, k),
             "ln": _init_norm(cfg, jax.random.fold_in(k, 1), cfg.d_model)}
            for k in ck])

    period = _pattern_period(cfg)
    n_super = cfg.n_layers // period
    bkeys = jax.random.split(keys[10], cfg.n_layers)
    supers = []
    for g in range(n_super):
        blk = {}
        for j in range(period):
            li = g * period + j
            blk[f"pos{j}"] = _init_block(cfg, bkeys[li], kinds[li])
        supers.append(blk)
    params["blocks"] = _stack(supers) if n_super > 1 else \
        jax.tree.map(lambda x: x[None], supers[0])
    return params


def _refuse_pattern(cfg: ModelConfig, what: str) -> None:
    """Block-pattern hybrids run only through the backend seam; every
    other path would run zamba2's shared-attention trunk instead."""
    if cfg.block_pattern:
        raise NotImplementedError(
            f"{what} does not run block-pattern hybrids ({cfg.name}); "
            "serve them through the backend seam on a paged cache")


def _pattern_period(cfg: ModelConfig) -> int:
    if cfg.family in ("ssm", "hybrid"):
        return 1
    if cfg.layer_pattern:
        return len(cfg.layer_pattern)
    if cfg.n_experts and cfg.moe_layer_period > 1:
        return cfg.moe_layer_period
    return 1


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               spec_only: bool = False) -> Dict:
    """Cache pytree (jnp zeros, or ShapeDtypeStructs when ``spec_only``)."""
    _refuse_pattern(cfg, "the scan-stacked cache")
    dt = _dtype(cfg)

    def mk(shape, dtype=dt):
        if spec_only:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.zeros(shape, dtype)

    cache: Dict = {"len": mk((), jnp.int32)}
    hd, hkv = cfg.hd, cfg.n_kv_heads

    if cfg.family in ("ssm", "hybrid"):
        period = cfg.shared_attn_period or cfg.n_layers
        n_groups = cfg.n_layers // period
        tail = cfg.n_layers - n_groups * period
        h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        gn2 = 2 * cfg.ssm_groups * cfg.ssm_state
        cache["ssm"] = mk((n_groups, period, batch, h, p, n), jnp.float32)
        cache["conv_x"] = mk((n_groups, period, batch, cfg.ssm_conv - 1,
                              cfg.d_inner))
        cache["conv_bc"] = mk((n_groups, period, batch, cfg.ssm_conv - 1,
                               gn2))
        if tail:
            cache["ssm_tail"] = mk((tail, batch, h, p, n), jnp.float32)
            cache["conv_x_tail"] = mk((tail, batch, cfg.ssm_conv - 1,
                                       cfg.d_inner))
            cache["conv_bc_tail"] = mk((tail, batch, cfg.ssm_conv - 1, gn2))
        if cfg.family == "hybrid":
            n_sites = len(cfg.shared_attn_sites())
            cache["shared_k"] = mk((n_sites, batch, hkv, max_len, hd))
            cache["shared_v"] = mk((n_sites, batch, hkv, max_len, hd))
        return cache

    period = _pattern_period(cfg)
    n_super = cfg.n_layers // period
    for j in range(period):
        if cfg.attn_kind == "mla":
            cache[f"lat{j}"] = mk((n_super, batch, max_len, cfg.kv_lora_rank))
            cache[f"kr{j}"] = mk((n_super, batch, max_len, cfg.qk_rope_dim))
        elif cfg.kv_dtype == "int8":
            # quantized cache: int8 values + per (token, head) scales
            cache[f"k{j}"] = mk((n_super, batch, hkv, max_len, hd), jnp.int8)
            cache[f"v{j}"] = mk((n_super, batch, hkv, max_len, hd), jnp.int8)
            cache[f"ks{j}"] = mk((n_super, batch, hkv, max_len), jnp.float32)
            cache[f"vs{j}"] = mk((n_super, batch, hkv, max_len), jnp.float32)
        else:
            # (stack, B, Hkv, T, hd): the attention dot consumes the cache
            # with no transpose (see layers._attend_block "bhtd")
            cache[f"k{j}"] = mk((n_super, batch, hkv, max_len, hd))
            cache[f"v{j}"] = mk((n_super, batch, hkv, max_len, hd))
    if cfg.family == "encdec":
        cache["cross_k"] = mk((cfg.n_layers, batch, cfg.encoder_seq, hkv, hd))
        cache["cross_v"] = mk((cfg.n_layers, batch, cfg.encoder_seq, hkv, hd))
    return cache


# ---------------------------------------------------------------------------
# Attention layer application (shared by train / prefill / decode)
# ---------------------------------------------------------------------------

def _apply_attn_layer(cfg, p, x, positions, *, kind: str,
                      kv_cache: Optional[Tuple] = None, cur_len=None,
                      rules: ShardingRules = NO_RULES,
                      cross_kv: Optional[Tuple] = None,
                      linear=None, kv_format: str = "bhtd",
                      norm_fn=None, attend_fn=None,
                      block_tables=None, paged_attend_fn=None):
    """Pre-norm attention + residual.  Returns (x, new_kv_cache).

    ``kv_cache`` is (k, v) buffers (B,T,...) to update at ``cur_len``;
    None during training (attend within the sequence only).

    ``linear`` is the pluggable matmul backend (see
    :mod:`repro.serving.backends`): every weight matmul of the layer is
    routed through it, so the same layer math serves both the resident
    jitted path (``None`` — weights read from ``p``) and the HeteGen
    offload engine.  ``kv_format`` is the cache layout: "bhtd" for the
    scan-stacked resident cache, "bthd" for the per-layer backend cache.
    ``norm_fn``/``attend_fn`` optionally replace the inline norm /
    attention with pre-jitted equivalents (the eager offload path keeps
    its small device pieces fused; see :func:`make_backend_ops`).

    With ``block_tables`` (B, nb), ``kv_cache`` holds *page pools*
    instead of dense buffers — (k_pages, v_pages) in (P, Hkv, ps, hd)
    layout, or (k, v, k_scale, v_scale) pools for q8 pages — written via
    :func:`_paged_write` and attended through :func:`_paged_attend` (or
    the pre-jitted ``paged_attend_fn``).
    """
    window = cfg.window if kind == "local" else None
    norm = norm_fn or (lambda pp, h: L.apply_norm(cfg, pp, h))
    h = norm(p["ln1"], x)

    if cfg.attn_kind == "mla":
        q_nope, q_rope = L.mla_project_q(cfg, p["attn"], h, positions)
        latent, k_rope = L.mla_latent_kv(cfg, p["attn"], h, positions)
        if kv_cache is None:
            out = L.mla_attend(cfg, p["attn"], q_nope, q_rope, latent,
                               k_rope, q_positions=positions,
                               kv_positions=positions, causal=True,
                               rules=rules)
            new_cache = None
        else:
            lat_buf, kr_buf = kv_cache
            lat_buf = _update_kv(lat_buf, latent, cur_len)
            kr_buf = _update_kv(kr_buf, k_rope, cur_len)
            t = lat_buf.shape[1]
            kvpos = jnp.arange(t)
            out = L.mla_attend(cfg, p["attn"], q_nope, q_rope, lat_buf,
                               kr_buf, q_positions=positions,
                               kv_positions=kvpos[None],
                               kv_len=cur_len + latent.shape[1],
                               causal=True, rules=rules)
            new_cache = (lat_buf, kr_buf)
    else:
        q, k, v = L.gqa_qkv(cfg, p["attn"], h, positions, rules,
                            linear=linear)
        if cross_kv is not None:
            k, v = cross_kv
            kvpos = jnp.arange(k.shape[1])
            out = L.attention(q, k, v, q_positions=positions,
                              kv_positions=kvpos[None], causal=False,
                              rules=rules)
            new_cache = None
        elif kv_cache is None:
            out = L.attention(q, k, v, q_positions=positions,
                              kv_positions=positions, causal=True,
                              window=window, attn_softcap=cfg.attn_softcap,
                              rules=rules)
            new_cache = None
        elif block_tables is not None:
            if len(kv_cache) == 4:      # q8 pools: int8 pages + scales
                k_pg, v_pg, ks_pg, vs_pg = kv_cache
                k_pg, ks_pg = _paged_write_q8(k_pg, ks_pg, k, block_tables,
                                              cur_len)
                v_pg, vs_pg = _paged_write_q8(v_pg, vs_pg, v, block_tables,
                                              cur_len)
                new_cache = (k_pg, v_pg, ks_pg, vs_pg)
                scales = (ks_pg, vs_pg)
            else:
                k_pg, v_pg = kv_cache
                k_pg = _paged_write(k_pg, k, block_tables, cur_len)
                v_pg = _paged_write(v_pg, v, block_tables, cur_len)
                new_cache = (k_pg, v_pg)
                scales = (None, None)
            pa = paged_attend_fn or functools.partial(_paged_attend, cfg)
            out = pa(q, k_pg, v_pg, block_tables, positions,
                     cur_len + k.shape[1], window, *scales)
        else:
            k_buf, v_buf = kv_cache     # (B, Hkv, T, D) or (B, T, Hkv, D)
            k_buf = _update_kv(k_buf, k, cur_len, layout=kv_format)
            v_buf = _update_kv(v_buf, v, cur_len, layout=kv_format)
            if attend_fn is not None:
                out = attend_fn(q, k_buf, v_buf, positions,
                                cur_len + k.shape[1], window)
            else:
                t = k_buf.shape[2] if kv_format == "bhtd" else k_buf.shape[1]
                kvpos = jnp.arange(t)
                out = L.attention(q, k_buf, v_buf, q_positions=positions,
                                  kv_positions=kvpos[None],
                                  kv_len=cur_len + k.shape[1], causal=True,
                                  window=window,
                                  attn_softcap=cfg.attn_softcap,
                                  kv_format=kv_format, rules=rules)
            new_cache = (k_buf, v_buf)
        out = L.attn_out(cfg, p["attn"], out, rules, linear=linear)

    if cfg.post_norm:
        out = norm(p["ln1_post"], out)
    return x + out, new_cache



def _apply_attn_layer_stacked(cfg, p, x, positions, *, kind: str, stacks,
                              li, cur_len, rules: ShardingRules = NO_RULES):
    """Like :func:`_apply_attn_layer` but against stacked (L, B, T, ...)
    cache buffers carried through the trunk scan: only the new token rows
    are written (in place); the layer's cache is sliced for attention.
    Returns (x, updated_stacks)."""
    window = cfg.window if kind == "local" else None
    h = L.apply_norm(cfg, p["ln1"], x)

    if cfg.attn_kind == "mla":
        q_nope, q_rope = L.mla_project_q(cfg, p["attn"], h, positions)
        latent, k_rope = L.mla_latent_kv(cfg, p["attn"], h, positions)
        lat_stack, kr_stack = stacks
        lat_stack = _stack_write(lat_stack, latent, li, cur_len)
        kr_stack = _stack_write(kr_stack, k_rope, li, cur_len)
        lat_buf = _stack_layer(lat_stack, li)
        kr_buf = _stack_layer(kr_stack, li)
        t = lat_buf.shape[1]
        kvpos = jnp.arange(t)
        out = L.mla_attend(cfg, p["attn"], q_nope, q_rope, lat_buf, kr_buf,
                           q_positions=positions, kv_positions=kvpos[None],
                           kv_len=cur_len + latent.shape[1], causal=True,
                           rules=rules)
        new_stacks = (lat_stack, kr_stack)
    else:
        q, k, v = L.gqa_qkv(cfg, p["attn"], h, positions, rules)
        if cfg.kv_dtype == "int8":
            (k_stack, v_stack, ks_stack, vs_stack) = stacks
            k_stack, ks_stack = _stack_write_q8(k_stack, ks_stack, k, li,
                                                cur_len)
            v_stack, vs_stack = _stack_write_q8(v_stack, vs_stack, v, li,
                                                cur_len)
            dt = jnp.dtype(cfg.dtype)
            k_buf = (_stack_layer(k_stack, li).astype(dt)
                     * _stack_layer(ks_stack, li)[..., None].astype(dt))
            v_buf = (_stack_layer(v_stack, li).astype(dt)
                     * _stack_layer(vs_stack, li)[..., None].astype(dt))
            new_stacks_q8 = (k_stack, v_stack, ks_stack, vs_stack)
        else:
            k_stack, v_stack = stacks
            k_stack = _stack_write(k_stack, k, li, cur_len, layout="bhtd")
            v_stack = _stack_write(v_stack, v, li, cur_len, layout="bhtd")
            k_buf = _stack_layer(k_stack, li)      # (B, Hkv, T, D)
            v_buf = _stack_layer(v_stack, li)
        kvpos = jnp.arange(k_buf.shape[2])
        out = L.attention(q, k_buf, v_buf, q_positions=positions,
                          kv_positions=kvpos[None],
                          kv_len=cur_len + k.shape[1], causal=True,
                          window=window, attn_softcap=cfg.attn_softcap,
                          kv_format="bhtd", rules=rules)
        out = L.attn_out(cfg, p["attn"], out, rules)
        new_stacks = new_stacks_q8 if cfg.kv_dtype == "int8" \
            else (k_stack, v_stack)

    if cfg.post_norm:
        out = L.apply_norm(cfg, p["ln1_post"], out)
    return x + out, new_stacks


def _apply_ffn(cfg, p, x, kind: str, rules: ShardingRules,
               aux: Optional[jax.Array] = None, linear=None, norm_fn=None):
    norm = norm_fn or (lambda pp, h: L.apply_norm(cfg, pp, h))
    h = norm(p["ln2"], x)
    if kind == "moe":
        y = L.moe(cfg, p["moe"], h, rules)
        if aux is not None:
            aux = aux + L.moe_aux_loss(cfg, p["moe"], h)
    else:
        y = L.mlp(cfg, p["mlp"], h, rules, linear=linear)
    if cfg.post_norm:
        y = norm(p["ln2_post"], y)
    return (x + y) if aux is None else (x + y, aux)


# ---------------------------------------------------------------------------
# Trunks
# ---------------------------------------------------------------------------

def _transformer_trunk(cfg, params, x, positions, *, cache=None, cur_len=None,
                       rules: ShardingRules = NO_RULES, remat=False):
    """Scan over super-blocks.  Returns (x, new_cache_dict)."""
    kinds = cfg.layer_kinds()
    period = _pattern_period(cfg)

    def block(carry, blk):
        x, aux = carry
        p_blk, kv_in = blk
        new_kv = {}
        for j in range(period):
            kind = kinds[j]
            kvc = None
            if kv_in is not None:
                if cfg.attn_kind == "mla":
                    kvc = (kv_in[f"lat{j}"], kv_in[f"kr{j}"])
                else:
                    kvc = (kv_in[f"k{j}"], kv_in[f"v{j}"])
            x, kv_out = _apply_attn_layer(cfg, p_blk[f"pos{j}"], x, positions,
                                          kind=kind, kv_cache=kvc,
                                          cur_len=cur_len, rules=rules)
            if kv_out is not None:
                if cfg.attn_kind == "mla":
                    new_kv[f"lat{j}"], new_kv[f"kr{j}"] = kv_out
                else:
                    new_kv[f"k{j}"], new_kv[f"v{j}"] = kv_out
            x, aux = _apply_ffn(cfg, p_blk[f"pos{j}"], x, kind, rules,
                                aux=aux)
            x = rules.act(x, "batch", "seq", "embed")
        return (x, aux), new_kv

    if remat:
        block = jax.checkpoint(block,
                               policy=jax.checkpoint_policies.nothing_saveable)

    kv_keys = [k for k in (cache or {})
               if any(k.startswith(pfx) and k[len(pfx):].isdigit()
                      for pfx in ("k", "v", "lat", "kr", "ks", "vs"))]

    if cache and not _legacy_cache_scan():
        # carry path: stacked caches updated in place (one token-row DUS
        # per layer) instead of copied through scan xs/ys
        def block_carry(carry, p_blk):
            x, aux, li, kvs = carry
            new_kvs = dict(kvs)
            for j in range(period):
                kind = kinds[j]
                if cfg.attn_kind == "mla":
                    stacks = (new_kvs[f"lat{j}"], new_kvs[f"kr{j}"])
                elif cfg.kv_dtype == "int8":
                    stacks = (new_kvs[f"k{j}"], new_kvs[f"v{j}"],
                              new_kvs[f"ks{j}"], new_kvs[f"vs{j}"])
                else:
                    stacks = (new_kvs[f"k{j}"], new_kvs[f"v{j}"])
                x, stacks = _apply_attn_layer_stacked(
                    cfg, p_blk[f"pos{j}"], x, positions, kind=kind,
                    stacks=stacks, li=li, cur_len=cur_len, rules=rules)
                if cfg.attn_kind == "mla":
                    new_kvs[f"lat{j}"], new_kvs[f"kr{j}"] = stacks
                elif cfg.kv_dtype == "int8":
                    (new_kvs[f"k{j}"], new_kvs[f"v{j}"],
                     new_kvs[f"ks{j}"], new_kvs[f"vs{j}"]) = stacks
                else:
                    new_kvs[f"k{j}"], new_kvs[f"v{j}"] = stacks
                x, aux = _apply_ffn(cfg, p_blk[f"pos{j}"], x, kind, rules,
                                    aux=aux)
                x = rules.act(x, "batch", "seq", "embed")
            return (x, aux, li + 1, new_kvs), ()

        kvs0 = {k: cache[k] for k in kv_keys}
        (x, aux, _, new_kv), _ = jax.lax.scan(
            block_carry,
            (x, jnp.zeros((), jnp.float32), jnp.int32(0), kvs0),
            params["blocks"])
        return x, new_kv, aux

    xs_cache = {k: cache[k] for k in kv_keys} if cache else None
    (x, aux), new_kv = jax.lax.scan(
        block, (x, jnp.zeros((), jnp.float32)), (params["blocks"], xs_cache))
    return x, new_kv, aux


def _mamba_trunk(cfg, params, x, positions, *, cache=None, cur_len=None,
                 rules: ShardingRules = NO_RULES, remat=False,
                 emb0=None):
    """SSM / hybrid trunk: scan over groups of ``period`` mamba layers,
    with the shared attention block applied at each group start (hybrid)."""
    period = cfg.shared_attn_period or cfg.n_layers
    n_groups = cfg.n_layers // period
    tail = cfg.n_layers - n_groups * period
    hybrid = cfg.family == "hybrid"

    def mamba_one(x, p, states):
        ssm_st, conv_st = states
        h = L.apply_norm(cfg, p["ln"], x)
        y, s2, c2 = S.mamba_block(cfg, p, h, ssm_state=ssm_st,
                                  conv_state=conv_st, rules=rules)
        return x + y, (s2, c2)

    def shared_block(x, site_idx, kv):
        p = params["shared"]
        h2 = jnp.concatenate([x, emb0], axis=-1)
        h = L.apply_norm(cfg, p["ln1"], h2)
        q, k, v = L.gqa_qkv(cfg, p["attn"], h, positions, rules)
        if "shared_lora" in params:
            la = params["shared_lora"]["a"][site_idx]
            lb = params["shared_lora"]["b"][site_idx]
            b_, s_, _ = h.shape
            dq = ((h @ la) @ lb).reshape(b_, s_, cfg.n_heads, cfg.hd)
            if cfg.pos_emb == "rope":
                dq = L.rope(dq, positions, cfg.rope_theta)
            q = q + dq
        if kv is None:
            out = L.attention(q, k, v, q_positions=positions,
                              kv_positions=positions, causal=True,
                              rules=rules)
            new_kv = None
        else:
            k_buf, v_buf = kv                  # (B, Hkv, T, D)
            k_buf = _update_kv(k_buf, k, cur_len, layout="bhtd")
            v_buf = _update_kv(v_buf, v, cur_len, layout="bhtd")
            kvpos = jnp.arange(k_buf.shape[2])
            out = L.attention(q, k_buf, v_buf, q_positions=positions,
                              kv_positions=kvpos[None],
                              kv_len=cur_len + k.shape[1], causal=True,
                              kv_format="bhtd", rules=rules)
            new_kv = (k_buf, v_buf)
        b_, s_, hq_, hd_ = out.shape
        h2 = h2 + out.reshape(b_, s_, hq_ * hd_) @ p["attn"]["wo"]
        hm = L.apply_norm(cfg, p["ln2"], h2)
        h2 = h2 + L.mlp(cfg, p["mlp"], hm, rules)
        return x + h2 @ p["proj"], new_kv

    def group(x, inp):
        gi, p_grp, states, kv = inp
        new_kv = None
        if hybrid:
            x, new_kv = shared_block(x, gi, kv)
        new_states = []
        for j in range(period):
            pj = jax.tree.map(lambda a: a[j], p_grp)
            stj = jax.tree.map(lambda a: a[j], states)
            x, st2 = mamba_one(x, pj, stj)
            new_states.append(st2)
        ssm_new = jnp.stack([st[0] for st in new_states])
        cx_new = jnp.stack([st[1][0] for st in new_states])
        cbc_new = jnp.stack([st[1][1] for st in new_states])
        return x, (ssm_new, cx_new, cbc_new, new_kv)

    if remat:
        group = jax.checkpoint(group,
                               policy=jax.checkpoint_policies.nothing_saveable)

    have_cache = cache is not None
    gn2 = 2 * cfg.ssm_groups * cfg.ssm_state
    if have_cache:
        states = (cache["ssm"], (cache["conv_x"], cache["conv_bc"]))
    else:
        states = (
            jnp.zeros((n_groups, period, x.shape[0], cfg.ssm_heads,
                       cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
            (jnp.zeros((n_groups, period, x.shape[0], cfg.ssm_conv - 1,
                        cfg.d_inner), _dtype(cfg)),
             jnp.zeros((n_groups, period, x.shape[0], cfg.ssm_conv - 1,
                        gn2), _dtype(cfg))))
    kvs = (cache.get("shared_k"), cache.get("shared_v")) if have_cache \
        else (None, None)

    def scan_body(x, inp):
        gi, p_grp, st_ssm, st_cx, st_cbc, k_b, v_b = inp
        kv = (k_b, v_b) if k_b is not None else None
        x, (s2, cx2, cbc2, kv2) = group(
            x, (gi, p_grp, (st_ssm, (st_cx, st_cbc)), kv))
        outs = {"ssm": s2, "conv_x": cx2, "conv_bc": cbc2}
        if kv2 is not None:
            outs["shared_k"], outs["shared_v"] = kv2
        return x, outs

    idx = jnp.arange(n_groups)
    have_kv = hybrid and kvs[0] is not None
    kv_xs_k = kvs[0][:n_groups] if have_kv else None
    kv_xs_v = kvs[1][:n_groups] if have_kv else None
    xs = (idx, params["blocks"], states[0], states[1][0], states[1][1],
          kv_xs_k, kv_xs_v)
    x, outs = jax.lax.scan(scan_body, x, xs)
    new_cache = dict(outs) if have_cache else {}

    # tail layers (eager, at most period-1 of them)
    if tail:
        tail_sites = hybrid and (n_groups * period in cfg.shared_attn_sites())
        if tail_sites:
            kv = None
            if have_cache:
                kv = (cache["shared_k"][n_groups], cache["shared_v"][n_groups])
            x, kv2 = shared_block(x, n_groups, kv)
            if have_cache and kv2 is not None:
                new_cache["shared_k"] = jnp.concatenate(
                    [new_cache["shared_k"], kv2[0][None]], axis=0)
                new_cache["shared_v"] = jnp.concatenate(
                    [new_cache["shared_v"], kv2[1][None]], axis=0)
        new_tail = []
        for t in range(tail):
            pj = jax.tree.map(lambda a: a[t], params["tail"])
            stj = (cache["ssm_tail"][t],
                   (cache["conv_x_tail"][t], cache["conv_bc_tail"][t])) \
                if have_cache else (None, None)
            x, st2 = mamba_one(x, pj, stj)
            new_tail.append(st2)
        if have_cache:
            new_cache["ssm_tail"] = jnp.stack([st[0] for st in new_tail])
            new_cache["conv_x_tail"] = jnp.stack([st[1][0]
                                                  for st in new_tail])
            new_cache["conv_bc_tail"] = jnp.stack([st[1][1]
                                                   for st in new_tail])
    return x, new_cache


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(cfg, params, tokens, rules: ShardingRules = NO_RULES):
    x = params["embed"][tokens]
    if cfg.emb_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return rules.act(x, "batch", "seq", "embed")


def lm_logits(cfg, params, x, rules: ShardingRules = NO_RULES):
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = x @ head
    logits = L.softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return rules.act(logits, "batch", "seq", "vocab")



import os as _os


def _legacy_cache_scan() -> bool:
    """Baseline A/B toggle for EXPERIMENTS.md §Perf: the legacy path
    threads per-layer caches through scan xs/ys, which copies every
    layer's full cache slice once per step.  The default (carry) path
    keeps stacked caches in the scan carry and writes only the new token
    rows in place."""
    return _os.environ.get("REPRO_LEGACY_CACHE_SCAN", "0") == "1"


def _scatter_pos(cur_len, b, s):
    """(B, s) write positions for a per-slot length vector: row i writes
    ``cur_len[i] + [0, s)``.  Paired with ``mode="drop"`` scatters so a
    padded row (speculative verify pads ragged drafts to one width) whose
    tail would run past the buffer writes nothing there."""
    return (jnp.asarray(cur_len, jnp.int32)[:, None]
            + jnp.arange(s, dtype=jnp.int32)[None])


def _stack_write(stack, new, li, cur_len, *, layout: str = "bthd"):
    """Write ``new`` (B, s, ...) into a stacked cache at layer ``li``,
    position ``cur_len`` (scalar, or a (B,) per-slot vector — continuous
    batching decode at s == 1, speculative verify at s > 1).

    layout "bthd": stack (L, B, T, ...) — MLA latents/rope keys.
    layout "bhtd": stack (L, B, H, T, D) — KV stacks in attention-native
    layout (no transpose on the read path)."""
    cl = jnp.asarray(cur_len)
    zero = jnp.int32(0)
    if layout == "bhtd":
        if cl.ndim == 0:
            new = jnp.swapaxes(new, 1, 2)      # (B,H,s,D)
            start = (jnp.asarray(li, jnp.int32), zero, zero,
                     cl.astype(jnp.int32), zero)
            return jax.lax.dynamic_update_slice(
                stack, new[None].astype(stack.dtype), start)
        b, s = new.shape[:2]
        pos = _scatter_pos(cl, b, s)
        # non-contiguous advanced indices: broadcast (B, s) dims lead, so
        # the slice's H lands after them — the value is (B, s, H, D) as-is
        return stack.at[li, jnp.arange(b)[:, None], :, pos].set(
            new.astype(stack.dtype), mode="drop")
    if cl.ndim == 0:
        start = (jnp.asarray(li, jnp.int32), zero, cl.astype(jnp.int32)) \
            + (zero,) * (stack.ndim - 3)
        return jax.lax.dynamic_update_slice(
            stack, new[None].astype(stack.dtype), start)
    b, s = new.shape[:2]
    pos = _scatter_pos(cl, b, s)
    return stack.at[li, jnp.arange(b)[:, None], pos].set(
        new.astype(stack.dtype), mode="drop")



def _quantize_kv(new):
    """(B,s,H,D) -> (int8 (B,H,s,D)-compatible values, scales (B,s,H))."""
    m = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(new.astype(jnp.float32) / m[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, m.astype(jnp.float32)


def _stack_write_q8(stack, scale_stack, new, li, cur_len):
    """Quantize ``new`` (B,s,H,D) and write into int8 stack + scale stack."""
    q, m = _quantize_kv(new)
    stack = _stack_write(stack, q, li, cur_len, layout="bhtd")
    # scales: (L,B,H,T): write m (B,s,H) -> (B,H,s)
    cl = jnp.asarray(cur_len)
    if cl.ndim == 0:
        zero = jnp.int32(0)
        ms = jnp.swapaxes(m, 1, 2)
        start = (jnp.asarray(li, jnp.int32), zero, zero, cl.astype(jnp.int32))
        scale_stack = jax.lax.dynamic_update_slice(
            scale_stack, ms[None].astype(scale_stack.dtype), start)
    else:
        b, s = m.shape[:2]
        pos = _scatter_pos(cl, b, s)
        scale_stack = scale_stack.at[li, jnp.arange(b)[:, None], :, pos].set(
            m.astype(scale_stack.dtype), mode="drop")
    return stack, scale_stack


def _stack_layer(stack, li):
    return jax.lax.dynamic_index_in_dim(stack, li, 0, keepdims=False)


def _paged_positions(block_tables, new, cur_len, page_size):
    """(page, offset) scatter coordinates for writing ``new`` (B, s, ...)
    into a page pool through ``block_tables`` (B, nb) at ``cur_len``
    (scalar, or a (B,) per-slot vector — decode at s == 1, speculative
    verify at s > 1).  Per-slot positions past the table's last block
    (a verify batch's padded rows near ``max_len``) are redirected to the
    trash page instead of clamping into a real one."""
    b, s = new.shape[:2]
    cl = jnp.asarray(cur_len, jnp.int32)
    if cl.ndim == 0:
        pos = cl + jnp.arange(s, dtype=jnp.int32)          # (s,)
        page = block_tables[:, pos // page_size]            # (B, s)
        off = jnp.broadcast_to((pos % page_size)[None], (b, s))
    else:
        pos = _scatter_pos(cl, b, s)                        # (B, s)
        blk = pos // page_size
        nb = block_tables.shape[1]
        page = jnp.take_along_axis(block_tables,
                                   jnp.minimum(blk, nb - 1), axis=1)
        page = jnp.where(blk < nb, page, 0)                 # trash page
        off = pos % page_size
    return page, off


def _paged_write(pages, new, block_tables, cur_len):
    """Scatter ``new`` (B, s, H, D) into a (P, H, page_size, D) pool.

    The paged counterpart of :func:`_update_kv`: physical pages come from
    the block table, so the write touches only the slot's own tokens —
    never a (B, max_len) slice.  Unmapped table entries point at the
    allocator's trash page, keeping masked garbage writes harmless.
    """
    page, off = _paged_positions(block_tables, new, cur_len, pages.shape[2])
    return pages.at[page, :, off].set(new.astype(pages.dtype))


def _paged_write_q8(pages, scale_pages, new, block_tables, cur_len):
    """Quantize ``new`` (B, s, H, D) and scatter into int8 pages plus
    per-(page, head, token) scale pages (P, H, page_size)."""
    q, m = _quantize_kv(new)
    page, off = _paged_positions(block_tables, new, cur_len, pages.shape[2])
    pages = pages.at[page, :, off].set(q)
    scale_pages = scale_pages.at[page, :, off].set(
        m.astype(scale_pages.dtype))
    return pages, scale_pages


def _paged_attend(cfg, q, k_pages, v_pages, block_tables, q_positions,
                  kv_len, window, k_scale=None, v_scale=None):
    """Attention over a paged cache.  Decode (s == 1, no window) runs the
    paged flash-decode kernel; everything else — prefill chunks starting
    at any offset, and windowed layers at any width — runs the paged
    flash-prefill kernel.  Both read K/V through the block table at HBM
    rate: the cache is never gathered into a dense (B, Hkv, T, D) buffer.
    """
    from repro.kernels import ops as K

    b, s = q.shape[:2]
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    if s == 1 and window is None:
        out = K.paged_decode_attention(q[:, 0], k_pages, v_pages,
                                       block_tables, lens,
                                       k_scale=k_scale, v_scale=v_scale,
                                       softcap=cfg.attn_softcap)
        return out[:, None]
    offs = q_positions[:, 0].astype(jnp.int32)             # (B,)
    out = K.paged_prefill_attention(jnp.swapaxes(q, 1, 2), k_pages, v_pages,
                                    block_tables, offs,
                                    k_scale=k_scale, v_scale=v_scale,
                                    softcap=cfg.attn_softcap, window=window)
    return jnp.swapaxes(out, 1, 2)


def _update_kv(buf, new, cur_len, *, layout: str = "bthd"):
    """Write ``new`` (B,s,H,D) into a cache buffer at ``cur_len``.

    ``layout`` "bthd": buf (B,T,H,D), seq axis 1 (offload runtime / MLA
    latents (B,T,R)).  "bhtd": buf (B,H,T,D), seq axis 2 (stacked KV).
    Scalar ``cur_len``: contiguous dynamic_update_slice; vector (B,):
    per-slot scatter (continuous-batching decode at s == 1, speculative
    verify at s > 1 — per-slot tails past the buffer are dropped).
    """
    cl = jnp.asarray(cur_len)
    if cl.ndim == 0:
        if layout == "bhtd":
            new = jnp.swapaxes(new, 1, 2)      # (B,H,s,D)
            axis = 2
        else:
            axis = 1
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), cl, axis=axis)
    b, s = new.shape[:2]
    pos = _scatter_pos(cl, b, s)
    rows = jnp.arange(b)[:, None]
    if layout == "bhtd":
        # broadcast advanced dims lead: value stays (B, s, H, D) as-is
        return buf.at[rows, :, pos].set(new.astype(buf.dtype), mode="drop")
    return buf.at[rows, pos].set(new.astype(buf.dtype), mode="drop")


def _positions_from(cur_len, b, s):
    base = jnp.arange(s, dtype=jnp.int32)[None, :]
    cl = jnp.asarray(cur_len, jnp.int32)
    if cl.ndim == 1:
        return cl[:, None] + base
    return cl + base + jnp.zeros((b, 1), jnp.int32)


def _add_learned_pos(cfg, params, x, positions):
    if cfg.pos_emb == "learned":
        x = x + params["pos"][positions]
    return x


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward_train(cfg: ModelConfig, params: Dict, batch: Dict,
                  rules: ShardingRules = NO_RULES,
                  return_aux: bool = False) -> jax.Array:
    """Full causal forward over a (B, S) batch -> logits (B, S, V).

    ``batch`` carries "tokens" and, for stub-frontend families, "embeds"
    (vlm: replaces token embeddings; encdec: encoder frames).
    """
    _refuse_pattern(cfg, "forward_train")
    if cfg.embeds_input and "embeds" in batch:
        x = batch["embeds"].astype(_dtype(cfg))
        b, s = x.shape[:2]
    else:
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_tokens(cfg, params, tokens, rules)
    positions = _positions_from(jnp.int32(0), b, s)
    x = _add_learned_pos(cfg, params, x, positions)

    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "encdec":
        enc = _encode(cfg, params, batch["enc_embeds"], rules)
        x, _ = _encdec_decoder(cfg, params, x, positions, enc, rules,
                               cache=None, cur_len=None)
    elif cfg.family in ("ssm", "hybrid"):
        emb0 = x if cfg.family == "hybrid" else None
        x, _ = _mamba_trunk(cfg, params, x, positions, rules=rules,
                            remat=cfg.remat, emb0=emb0)
    else:
        x, _, aux = _transformer_trunk(cfg, params, x, positions, rules=rules,
                                       remat=cfg.remat)
    x = L.apply_norm(cfg, params["final_norm"], x)
    logits = lm_logits(cfg, params, x, rules)
    if return_aux:
        return logits, aux
    return logits


def _encode(cfg, params, enc_embeds, rules):
    x = enc_embeds.astype(_dtype(cfg))
    b, s = x.shape[:2]
    x = x + params["enc_pos"][None, :s]
    positions = _positions_from(jnp.int32(0), b, s)

    def body(x, p):
        h = L.apply_norm(cfg, p["ln1"], x)
        q, k, v = L.gqa_qkv(cfg, p["attn"], h, positions, rules)
        out = L.attention(q, k, v, q_positions=positions,
                          kv_positions=positions, causal=False, rules=rules)
        x = x + L.attn_out(cfg, p["attn"], out, rules)
        x = _apply_ffn(cfg, p, x, "dense", rules)
        return x, ()

    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return L.apply_norm(cfg, params["enc_final_norm"], x)


def _encdec_decoder(cfg, params, x, positions, enc, rules, *, cache,
                    cur_len):
    """Decoder with self attention (+cache) and cross attention to ``enc``
    (or to cached cross K/V when ``enc`` is None)."""
    def body(x, inp):
        p_blk, pc, kv_in, cross_in = inp
        kvc = (kv_in["k0"], kv_in["v0"]) if kv_in is not None else None
        x, kv_out = _apply_attn_layer(cfg, p_blk["pos0"], x, positions,
                                      kind="dense", kv_cache=kvc,
                                      cur_len=cur_len, rules=rules)
        # cross attention
        hx = L.apply_norm(cfg, pc["ln"], x)
        q, ck, cv = L.gqa_qkv(cfg, pc["attn"], hx, positions, rules)
        if cross_in is not None:
            ck, cv = cross_in
        kvpos = jnp.arange(ck.shape[1])
        out = L.attention(q, ck, cv, q_positions=positions,
                          kv_positions=kvpos[None], causal=False, rules=rules)
        x = x + L.attn_out(cfg, pc["attn"], out, rules)
        x = _apply_ffn(cfg, p_blk["pos0"], x, "dense", rules)
        outs = {}
        if kv_out is not None:
            outs["k0"], outs["v0"] = kv_out
        if cross_in is None:
            outs["cross_k"], outs["cross_v"] = ck, cv
        return x, outs

    kv_xs = None
    cross_xs = None
    if cache is not None:
        kv_xs = {"k0": cache["k0"], "v0": cache["v0"]}
        if enc is None:
            cross_xs = (cache["cross_k"], cache["cross_v"])

    if enc is not None and cache is not None:
        # prefill: compute cross K/V from encoder output, store them
        def body_with_enc(x, inp):
            p_blk, pc, kv_in = inp
            kvc = (kv_in["k0"], kv_in["v0"])
            x, kv_out = _apply_attn_layer(cfg, p_blk["pos0"], x, positions,
                                          kind="dense", kv_cache=kvc,
                                          cur_len=cur_len, rules=rules)
            hx = L.apply_norm(cfg, pc["ln"], x)
            q, _, _ = L.gqa_qkv(cfg, pc["attn"], hx, positions, rules)
            encpos = _positions_from(jnp.int32(0), enc.shape[0], enc.shape[1])
            _, ck, cv = L.gqa_qkv(cfg, pc["attn"], enc, encpos, rules)
            kvpos = jnp.arange(ck.shape[1])
            out = L.attention(q, ck, cv, q_positions=positions,
                              kv_positions=kvpos[None], causal=False,
                              rules=rules)
            x = x + L.attn_out(cfg, pc["attn"], out, rules)
            x = _apply_ffn(cfg, p_blk["pos0"], x, "dense", rules)
            return x, {"k0": kv_out[0], "v0": kv_out[1],
                       "cross_k": ck, "cross_v": cv}

        x, outs = jax.lax.scan(body_with_enc, x,
                               (params["blocks"], params["cross"], kv_xs))
        return x, outs

    if enc is not None:
        # training: cross K/V recomputed per layer from enc
        def body_train(x, inp):
            p_blk, pc = inp
            x, _ = _apply_attn_layer(cfg, p_blk["pos0"], x, positions,
                                     kind="dense", kv_cache=None,
                                     cur_len=None, rules=rules)
            hx = L.apply_norm(cfg, pc["ln"], x)
            q, _, _ = L.gqa_qkv(cfg, pc["attn"], hx, positions, rules)
            encpos = _positions_from(jnp.int32(0), enc.shape[0], enc.shape[1])
            _, ck, cv = L.gqa_qkv(cfg, pc["attn"], enc, encpos, rules)
            kvpos = jnp.arange(ck.shape[1])
            out = L.attention(q, ck, cv, q_positions=positions,
                              kv_positions=kvpos[None], causal=False,
                              rules=rules)
            x = x + L.attn_out(cfg, pc["attn"], out, rules)
            x = _apply_ffn(cfg, p_blk["pos0"], x, "dense", rules)
            return x, ()

        body_fn = body_train
        if cfg.remat:
            body_fn = jax.checkpoint(
                body_fn, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(body_fn, x, (params["blocks"], params["cross"]))
        return x, {}

    # decode: use cached cross K/V
    x, outs = jax.lax.scan(body, x, (params["blocks"], params["cross"],
                                     kv_xs, cross_xs))
    return x, outs


def prefill(cfg: ModelConfig, params: Dict, batch: Dict, cache: Dict,
            rules: ShardingRules = NO_RULES,
            all_logits: bool = False) -> Tuple[Dict, jax.Array]:
    """Process the prompt, fill the cache, return (cache, last_logits).

    ``all_logits=True`` returns logits for EVERY position, (B, S, V)
    instead of (B, V) — the speculative-verify shape, where one
    prefill-shaped pass must score each draft position's next-token
    distribution."""
    _refuse_pattern(cfg, "the scan-stacked prefill")
    if cfg.embeds_input and "embeds" in batch:
        x = batch["embeds"].astype(_dtype(cfg))
        b, s = x.shape[:2]
    else:
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_tokens(cfg, params, tokens, rules)
    cur_len = cache["len"]
    positions = _positions_from(cur_len, b, s)
    x = _add_learned_pos(cfg, params, x, positions)

    new_cache = dict(cache)
    if cfg.family == "encdec":
        # prefill carries encoder frames; decode reuses the cached cross K/V
        enc = None
        if "enc_embeds" in batch:
            enc = _encode(cfg, params, batch["enc_embeds"], rules)
        x, outs = _encdec_decoder(cfg, params, x, positions, enc, rules,
                                  cache=cache, cur_len=cur_len)
        new_cache.update(outs)
    elif cfg.family in ("ssm", "hybrid"):
        emb0 = x if cfg.family == "hybrid" else None
        x, outs = _mamba_trunk(cfg, params, x, positions, cache=cache,
                               cur_len=cur_len, rules=rules, emb0=emb0)
        new_cache.update(outs)
    else:
        x, outs, _ = _transformer_trunk(cfg, params, x, positions,
                                        cache=cache, cur_len=cur_len,
                                        rules=rules)
        new_cache.update(outs)
    new_cache["len"] = cur_len + s
    x = L.apply_norm(cfg, params["final_norm"],
                     x if all_logits else x[:, -1:])
    logits = lm_logits(cfg, params, x, rules)
    return new_cache, (logits if all_logits else logits[:, 0])


def decode_step(cfg: ModelConfig, params: Dict, token: jax.Array,
                cache: Dict, rules: ShardingRules = NO_RULES
                ) -> Tuple[Dict, jax.Array]:
    """One decode step: token (B,) int32 -> (cache, logits (B, V))."""
    batch = {"tokens": token[:, None]}
    new_cache, logits = prefill(cfg, params, batch, cache, rules)
    return new_cache, logits


# ---------------------------------------------------------------------------
# Backend-parameterized execution — one layer-math core, pluggable linears
# ---------------------------------------------------------------------------
#
# The functions below drive the SAME per-layer math as the jitted scan trunk
# (_apply_attn_layer / _apply_ffn / layers.gqa_qkv / layers.mlp), but with
# every weight matmul routed through an injected ``linear(x, name)``
# callable.  A resident backend implements ``linear`` as a device matmul
# over its own weight inventory; the HeteGen backend implements it as the
# engine's alpha-split host/device execution (repro.serving.backends).

def decoder_layer(cfg, p, x, positions, *, kv_cache, cur_len, linear,
                  kind: str = "dense", rules: ShardingRules = NO_RULES,
                  ops: Optional[Dict] = None, block_tables=None):
    """One full decoder layer (attention + FFN), backend-parameterized.

    ``kv_cache`` is this layer's (k, v) buffers in (B, T, Hkv, hd) layout;
    ``cur_len`` is a scalar, or a (B,) per-slot length vector for
    continuous batching.  ``ops`` optionally carries pre-jitted "norm" /
    "attend" device pieces (:func:`make_backend_ops`) for eager drivers.
    Returns (x, (k_buf, v_buf)).

    With ``block_tables`` the layer runs against paged page pools instead
    (``kv_cache`` = (k_pages, v_pages[, k_scale, v_scale]); see
    :mod:`repro.serving.kv_cache`): writes scatter through the block
    table and decode attends via the paged flash-decode kernel.
    """
    ops = ops or {}
    x, new_kv = _apply_attn_layer(cfg, p, x, positions, kind=kind,
                                  kv_cache=kv_cache, cur_len=cur_len,
                                  rules=rules, linear=linear,
                                  kv_format="bthd",
                                  norm_fn=ops.get("norm"),
                                  attend_fn=ops.get("attend"),
                                  block_tables=block_tables,
                                  paged_attend_fn=ops.get("paged_attend"))
    x = _apply_ffn(cfg, p, x, kind, rules, linear=linear,
                   norm_fn=ops.get("norm"))
    return x, new_kv


def make_backend_ops(cfg: ModelConfig) -> Dict:
    """Jitted device pieces for the eager offload driver: norms, the
    attention core (per-layer window is a static arg), and the lm head —
    the small on-device math between engine linears stays fused, as in the
    pre-seam offload runtime."""
    from functools import partial

    def _attend(q, k_buf, v_buf, q_positions, kv_len, window):
        kvpos = jnp.arange(k_buf.shape[1])
        return L.attention(q, k_buf, v_buf, q_positions=q_positions,
                           kv_positions=kvpos[None], kv_len=kv_len,
                           causal=True, window=window,
                           attn_softcap=cfg.attn_softcap, kv_format="bthd")

    def _paged(q, k_pages, v_pages, block_tables, q_positions, kv_len,
               window, k_scale=None, v_scale=None):
        return _paged_attend(cfg, q, k_pages, v_pages, block_tables,
                             q_positions, kv_len, window,
                             k_scale=k_scale, v_scale=v_scale)

    def _logits(shared, x):
        return lm_logits(cfg, shared, x)

    ops = {"norm": jax.jit(partial(L.apply_norm, cfg)),
           "attend": jax.jit(_attend, static_argnums=(5,)),
           "paged_attend": jax.jit(_paged, static_argnums=(6,)),
           "logits": jax.jit(_logits)}
    if cfg.block_pattern:
        # the Mamba2 mixer's device pieces; each updates its state pools
        # in place (donated), so a step never copies a pool
        ops.update(
            ssm_conv=jax.jit(partial(ssm_prefill_conv, cfg),
                             donate_argnums=(2,)),
            ssm_scan=jax.jit(partial(ssm_prefill_scan, cfg),
                             donate_argnums=(3,)),
            ssm_step=jax.jit(partial(ssm_state_step, cfg),
                             donate_argnums=(2, 3)))
    return ops


def extract_backend_params(cfg: ModelConfig, params: Dict):
    """Split a stacked param pytree into (shared, weights, biases).

    ``weights``/``biases`` map flat linear names ("blk{l}.wq", ...) to
    per-layer arrays — the inventory a LinearBackend executes; ``shared``
    keeps everything the layer math reads directly (embeddings, norms,
    qk-norm scales, lm head) plus per-layer small-param dicts under
    "layers".

    Block-pattern hybrids (nemotron-h) keep their per-layer list: a Mamba
    layer contributes "blk{l}.in_proj"/"blk{l}.out_proj" and keeps its
    conv, A, D, dt bias and gated-norm scale in ``shared``.
    """
    if cfg.block_pattern:
        return _extract_pattern_params(cfg, params)
    if cfg.family not in ("dense", "vlm") or cfg.attn_kind != "gqa":
        raise NotImplementedError(
            "backend execution supports dense GQA decoders "
            f"(got family={cfg.family}, attn={cfg.attn_kind})")
    period = _pattern_period(cfg)
    weights: Dict = {}
    biases: Dict = {}
    shared: Dict = {"embed": params["embed"],
                    "final_norm": params["final_norm"]}
    for kname in ("lm_head", "pos"):
        if kname in params:
            shared[kname] = params[kname]
    supers = [jax.tree.map(lambda a, _g=g: a[_g], params["blocks"])
              for g in range(cfg.n_layers // period)]
    layers = []
    for l in range(cfg.n_layers):
        g, j = divmod(l, period)
        blk = supers[g][f"pos{j}"]
        a, m = blk["attn"], blk.get("mlp", {})
        for nm in ("wq", "wk", "wv", "wo"):
            weights[f"blk{l}.{nm}"] = a[nm]
        if cfg.attn_bias:
            for nm, bk in (("wq", "bq"), ("wk", "bk"), ("wv", "bv"),
                           ("wo", "bo")):
                biases[f"blk{l}.{nm}"] = a[bk]
        for nm in ("w_gate", "w_up", "w_down", "w_in"):
            if nm in m:
                weights[f"blk{l}.{nm}"] = m[nm]
        if cfg.attn_bias and "b_in" in m:
            biases[f"blk{l}.w_in"] = m["b_in"]
            biases[f"blk{l}.w_down"] = m["b_down"]
        small = {"ln1": blk["ln1"], "ln2": blk["ln2"],
                 "attn": {}, "mlp": {}}
        if cfg.post_norm:
            small["ln1_post"] = blk["ln1_post"]
            small["ln2_post"] = blk["ln2_post"]
        if cfg.qk_norm:
            small["attn"] = {"q_norm": a["q_norm"], "k_norm": a["k_norm"]}
        layers.append(small)
    shared["layers"] = layers
    return shared, weights, biases


def _extract_pattern_params(cfg: ModelConfig, params: Dict):
    if cfg.attn_kind != "gqa" or cfg.attn_bias or cfg.qk_norm \
            or cfg.post_norm or cfg.mlp_kind.startswith("gated"):
        raise NotImplementedError(
            f"{cfg.name}: block-pattern layers take GQA attention and a "
            "plain MLP with no biases, qk-norm or post-norms")
    shared: Dict = {"embed": params["embed"],
                    "final_norm": params["final_norm"]}
    if "lm_head" in params:
        shared["lm_head"] = params["lm_head"]
    weights: Dict = {}
    layers = []
    for l, (kind, blk) in enumerate(zip(cfg.layer_kinds(),
                                        params["layers"])):
        small: Dict = {"norm": blk["norm"]}
        if kind == "mamba":
            m = blk["mixer"]
            weights[f"blk{l}.in_proj"] = m["in_proj"]
            weights[f"blk{l}.out_proj"] = m["out_proj"]
            small["mixer"] = {k: v for k, v in m.items()
                              if k not in ("in_proj", "out_proj")}
        elif kind == "attn":
            for nm in ("wq", "wk", "wv", "wo"):
                weights[f"blk{l}.{nm}"] = blk["attn"][nm]
        else:
            for nm in ("w_in", "w_down"):
                weights[f"blk{l}.{nm}"] = blk["mlp"][nm]
        layers.append(small)
    shared["layers"] = layers
    return shared, weights, {}


# -- the Mamba2 mixer of block-pattern layers, on the device ----------------
#
# ``in_proj`` and ``out_proj`` run through the backend's ``linear`` (the
# HeteGen split); what lies between them runs here, against two per-slot
# state pools (:mod:`repro.serving.kv_cache`): ``conv_pool`` (S, K-1, C)
# holds each slot's last K-1 conv inputs and ``ssm_pool`` (S, H, P, N) its
# SSM state.  ``slots`` (B,) names the pool row of each batch row; the
# functions read those rows and write them back, nothing else.

def _ssm_dims(cfg):
    gn = cfg.ssm_groups * cfg.ssm_state
    return cfg.d_inner, cfg.ssm_conv_dim, gn


def _ssm_split(cfg, zxbcdt):
    """[z | x B C | dt] as in_proj emits it."""
    din, cd, _ = _ssm_dims(cfg)
    return (zxbcdt[..., :din], zxbcdt[..., din:din + cd],
            zxbcdt[..., din + cd:])


def _gated_norm(cfg, y, z, scale):
    """RMSNorm of y * silu(z) over each group of d_inner / ssm_groups
    channels (the gate before the norm), times ``scale``."""
    yz = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shp = yz.shape
    yz = yz.reshape(shp[:-1] + (cfg.ssm_groups, shp[-1] // cfg.ssm_groups))
    var = jnp.mean(jnp.square(yz), axis=-1, keepdims=True)
    yz = (yz * jax.lax.rsqrt(var + cfg.norm_eps)).reshape(shp)
    return (yz * scale.astype(jnp.float32)).astype(y.dtype)


def ssm_prefill_conv(cfg, p, zxbcdt, conv_pool, slots):
    """Causal conv over a prompt chunk (B, s, ...) from each slot's conv
    state; returns (silu(conv) of the x B C channels, updated pool)."""
    _, xbc, _ = _ssm_split(cfg, zxbcdt)
    y, new = S._causal_conv(xbc, p["conv_w"], p["conv_b"],
                            conv_pool[slots].astype(xbc.dtype))
    return y, conv_pool.at[slots].set(new.astype(conv_pool.dtype))


def ssm_prefill_scan(cfg, p, zxbcdt, xbc, ssm_pool, slots):
    """SSD over a prompt chunk from each slot's SSM state (chunked form,
    the chunk padded at its end with dt = 0 steps, which leave the state
    as it is); returns (gated-normed y (B, s, d_inner), updated pool)."""
    z, _, dt = _ssm_split(cfg, zxbcdt)
    din, _, gn = _ssm_dims(cfg)
    b, s = xbc.shape[:2]
    h, pd, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, \
        cfg.ssm_state
    x = xbc[..., :din].reshape(b, s, h, pd)
    bm = xbc[..., din:din + gn].reshape(b, s, g, n)
    cm = xbc[..., din + gn:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    pad = (-s) % cfg.ssm_chunk
    if pad:
        def tail(a):
            return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, dt, bm, cm = tail(x), tail(dt), tail(bm), tail(cm)
    y, h_n = S.ssd_chunked(x, dt, -jnp.exp(p["A_log"]), bm, cm, p["D"],
                           chunk=cfg.ssm_chunk, h0=ssm_pool[slots])
    y = y[:, :s].reshape(b, s, din)
    return (_gated_norm(cfg, y, z, p["gnorm"]),
            ssm_pool.at[slots].set(h_n))


def ssm_state_step(cfg, p, zxbcdt, conv_pool, ssm_pool, slots):
    """One decode token (B, 1, ...) through conv and SSM, each slot's
    state updated in place (kernels ``ssm_state_conv`` and
    ``ssm_state_scan``); returns (gated-normed y (B, 1, d_inner), conv
    pool, SSM pool)."""
    from repro.kernels import ops as K

    z, xbc, dt = _ssm_split(cfg, zxbcdt[:, 0])
    din, _, gn = _ssm_dims(cfg)
    b = xbc.shape[0]
    h, pd, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, \
        cfg.ssm_state
    xbc, conv_pool = K.ssm_conv_step(conv_pool, slots, xbc, p["conv_w"],
                                     p["conv_b"])
    rep = h // g
    bm = jnp.repeat(xbc[:, din:din + gn].reshape(b, g, n), rep, axis=1)
    cm = jnp.repeat(xbc[:, din + gn:].reshape(b, g, n), rep, axis=1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    y, ssm_pool = K.ssm_scan_step(ssm_pool, slots,
                                  xbc[:, :din].reshape(b, h, pd), dt, bm,
                                  cm, -jnp.exp(p["A_log"]), p["D"])
    y = _gated_norm(cfg, y.reshape(b, din), z, p["gnorm"])
    return y[:, None], conv_pool, ssm_pool


def _mamba_mixer(cfg, p, h, conv_pool, ssm_pool, slots, linear, ops):
    """in_proj (backend) -> conv + SSD (device) -> out_proj (backend)."""
    zxbcdt = linear(h, "in_proj")
    if h.shape[1] == 1:
        step = ops.get("ssm_step") or functools.partial(ssm_state_step, cfg)
        y, conv_pool, ssm_pool = step(p, zxbcdt, conv_pool, ssm_pool, slots)
    else:
        conv = ops.get("ssm_conv") \
            or functools.partial(ssm_prefill_conv, cfg)
        scan = ops.get("ssm_scan") \
            or functools.partial(ssm_prefill_scan, cfg)
        xbc, conv_pool = conv(p, zxbcdt, conv_pool, slots)
        y, ssm_pool = scan(p, zxbcdt, xbc, ssm_pool, slots)
    return linear(y, "out_proj"), conv_pool, ssm_pool


def _pattern_layers(cfg, shared, x, positions, cache, cur_len, *, linear,
                    ops):
    """Run every block-pattern layer: x + mixer(norm(x)), the mixer a
    Mamba2 mixer, paged GQA attention or an MLP.  Returns (x, cache)."""
    if "block_tables" not in cache:
        raise NotImplementedError(
            f"{cfg.name}: block-pattern layers run on a paged cache only "
            "(PagedKVCache holds their pages and per-slot state)")
    new_cache = dict(cache)
    norm = ops.get("norm") or (lambda pp, h: L.apply_norm(cfg, pp, h))
    slots = cache["slot_rows"]
    for l, kind in enumerate(cfg.layer_kinds()):
        p = shared["layers"][l]
        lin = (lambda h, nm, _l=l: linear(h, f"blk{_l}.{nm}"))
        if kind == "attn":
            x, kv = _apply_attn_layer(
                cfg, {"ln1": p["norm"], "attn": {}}, x, positions,
                kind="global", kv_cache=(cache[f"pages_k{l}"],
                                         cache[f"pages_v{l}"]),
                cur_len=cur_len, linear=lin, norm_fn=ops.get("norm"),
                block_tables=cache["block_tables"],
                paged_attend_fn=ops.get("paged_attend"))
            new_cache[f"pages_k{l}"], new_cache[f"pages_v{l}"] = kv
        elif kind == "mlp":
            x = _apply_ffn(cfg, {"ln2": p["norm"], "mlp": {}}, x, "dense",
                           NO_RULES, linear=lin, norm_fn=ops.get("norm"))
        else:
            y, conv, ssm = _mamba_mixer(
                cfg, p["mixer"], norm(p["norm"], x),
                cache[f"state_conv{l}"], cache[f"state_ssm{l}"], slots,
                lin, ops)
            new_cache[f"state_conv{l}"], new_cache[f"state_ssm{l}"] = \
                conv, ssm
            x = x + y
    return x, new_cache


def init_backend_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Per-layer KV cache for backend execution: "k{l}"/"v{l}" buffers in
    (B, T, Hkv, hd) layout plus "len" (scalar; continuous batching replaces
    it with a (B,) per-slot vector).  Batch lives on axis 0 of every
    buffer.  The paged alternative (no dense (B, T) buffers) is minted by
    :meth:`repro.serving.kv_cache.PagedKVCache.init_cache`."""
    _refuse_pattern(cfg, "the dense backend cache")
    dt = _dtype(cfg)
    cache: Dict = {"len": jnp.zeros((), jnp.int32)}
    for l in range(cfg.n_layers):
        cache[f"k{l}"] = jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.hd),
                                   dt)
        cache[f"v{l}"] = jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.hd),
                                   dt)
    return cache


def backend_prefill(cfg: ModelConfig, shared: Dict, batch: Dict, cache: Dict,
                    *, linear, ops: Optional[Dict] = None,
                    all_logits: bool = False) -> Tuple[Dict, jax.Array]:
    """Prompt/step processing through the shared layer math with all
    linears routed through ``linear(x, "blk{l}.{name}")``.  Mirrors
    :func:`prefill` for the dense GQA families.  ``ops`` carries the
    pre-jitted device pieces for eager drivers (:func:`make_backend_ops`).
    ``all_logits=True`` returns (B, S, V) per-position logits — the
    speculative-verify shape.

    A cache holding "pages_k{l}"/"pages_v{l}" pools plus "block_tables"
    (from :class:`repro.serving.kv_cache.PagedKVCache`) switches every
    layer to the paged plumbing; "pages_ks{l}"/"pages_vs{l}" scale pools
    additionally select q8 (int8-page) writes."""
    ops = ops or {}
    if cfg.embeds_input and "embeds" in batch:
        x = batch["embeds"].astype(_dtype(cfg))
        b, s = x.shape[:2]
    else:
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed_tokens(cfg, shared, tokens)
    cur_len = cache["len"]
    positions = _positions_from(cur_len, b, s)
    x = _add_learned_pos(cfg, shared, x, positions)
    if cfg.block_pattern:
        x, new_cache = _pattern_layers(cfg, shared, x, positions, cache,
                                       cur_len, linear=linear, ops=ops)
        return _backend_head(cfg, shared, x, new_cache, cur_len + s, ops,
                             all_logits)
    kinds = cfg.layer_kinds()
    new_cache = dict(cache)
    paged = "pages_k0" in cache         # paged pools instead of dense bufs
    bt = cache.get("block_tables")
    q8 = "pages_ks0" in cache
    for l in range(cfg.n_layers):
        lin = (lambda h, nm, _l=l: linear(h, f"blk{_l}.{nm}"))
        if paged:
            kvc = (cache[f"pages_k{l}"], cache[f"pages_v{l}"])
            if q8:
                kvc += (cache[f"pages_ks{l}"], cache[f"pages_vs{l}"])
        else:
            kvc = (cache[f"k{l}"], cache[f"v{l}"])
        x, kv = decoder_layer(cfg, shared["layers"][l], x, positions,
                              kv_cache=kvc, cur_len=cur_len, linear=lin,
                              kind=kinds[l], ops=ops,
                              block_tables=bt if paged else None)
        if paged:
            new_cache[f"pages_k{l}"], new_cache[f"pages_v{l}"] = kv[:2]
            if q8:
                (new_cache[f"pages_ks{l}"],
                 new_cache[f"pages_vs{l}"]) = kv[2:]
        else:
            new_cache[f"k{l}"], new_cache[f"v{l}"] = kv
    return _backend_head(cfg, shared, x, new_cache, cur_len + s, ops,
                         all_logits)


def _backend_head(cfg, shared, x, new_cache, new_len, ops, all_logits):
    """Final norm and head after the layers; sets the cache length."""
    new_cache["len"] = new_len
    norm = ops.get("norm") or (lambda pp, h: L.apply_norm(cfg, pp, h))
    x = norm(shared["final_norm"], x if all_logits else x[:, -1:])
    if "logits" in ops:
        logits = ops["logits"](shared, x)
    else:
        logits = lm_logits(cfg, shared, x)
    return new_cache, (logits if all_logits else logits[:, 0])


def backend_decode(cfg: ModelConfig, shared: Dict, token: jax.Array,
                   cache: Dict, *, linear, ops: Optional[Dict] = None
                   ) -> Tuple[Dict, jax.Array]:
    """One decode step through the backend seam: token (B,) -> logits."""
    return backend_prefill(cfg, shared, {"tokens": token[:, None]}, cache,
                           linear=linear, ops=ops)
