"""nemotron-h-47b [hybrid] — 98 layers by block pattern: 45 Mamba-2
mixers (M), 48 squared-ReLU MLPs (-) and 5 GQA attention layers (*),
d_model=8192, vocab=131072, untied head.  [Nemotron-H, arXiv:2504.03624;
nvidia/Nemotron-H-47B-Base-8K config.json]

Mamba-2: 256 heads of 64 (d_inner 16384), state 256, 8 groups (the gated
RMSNorm normalizes each group of 2048 channels), causal conv of 4 with
bias, SSD chunk 128.  Attention: 64 query heads over 8 K/V heads of 128,
no positional embedding.  MLP: up 8192 -> 30720, relu(.)^2, down; no
bias anywhere.  RMSNorm eps 1e-5.
"""
from repro.configs import register
from repro.models.config import ModelConfig

PATTERN = ("M-M-M-M-M-M-M-M-M*-M-M-M-M-M-M-M-M-M-M*-M-M-M-M-M*-M-M-M-M-M*"
           "-M-M-M-M-M-M-M---MM---M-M*-M-M-M-M-M-")

CONFIG = register(ModelConfig(
    name="nemotron-h-47b",
    family="hybrid",
    block_pattern=PATTERN,
    n_layers=len(PATTERN),
    d_model=8192,
    n_heads=64,
    n_kv_heads=8,
    head_dim=128,
    d_ff=30720,
    vocab_size=131072,
    attn_kind="gqa",
    pos_emb="none",
    max_seq=8192,
    mlp_kind="relu2",
    ssm_state=256,
    ssm_conv=4,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_chunk=128,
    ssm_groups=8,
    norm_kind="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=False,
    attn_bias=False,
))
