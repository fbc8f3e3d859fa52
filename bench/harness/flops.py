"""Operations and bytes of the work the program does, from shapes alone.

Kept with the benchmark so that no change to the program can change how
its work is counted.  Counts are what the algorithm needs, never more:
a roofline share computed from them cannot pass 100% unless a time leaves
out part of the work.
"""

from __future__ import annotations

from typing import Dict, Iterable


def linear_params_per_layer(sizes: Dict) -> int:
    """Weights of one OPT decoder layer's linears (q, k, v, o, in, down)."""
    d, f = sizes["hidden_size"], sizes["ffn_dim"]
    return 4 * d * d + 2 * d * f


def model_flops(sizes: Dict, tokens: int, head_rows: int) -> float:
    """Model FLOPs of ``tokens`` positions through every layer's linears,
    plus ``head_rows`` rows through the tied output head (2 per
    multiply-add).  Attention scores are left out, so this never counts
    more than the program did."""
    L = sizes["num_hidden_layers"]
    d, V = sizes["hidden_size"], sizes["vocab_size"]
    return 2.0 * tokens * L * linear_params_per_layer(sizes) \
        + 2.0 * head_rows * d * V


def paged_decode_bytes(sizes: Dict, kv_lens: Iterable[int],
                       kv_bytes: int = 4) -> float:
    """Bytes one paged decode-attention call must read and write: each
    row's keys and values up to its length, its query and its output."""
    d = sizes["hidden_size"]
    rows = list(kv_lens)
    return float(sum(2 * n * d * kv_bytes for n in rows)
                 + 2 * len(rows) * d * 4)


def paged_decode_flops(sizes: Dict, kv_lens: Iterable[int]) -> float:
    """Scores and weighted sum: 4 FLOPs per head dimension per key."""
    d = sizes["hidden_size"]
    return float(sum(4 * n * d for n in kv_lens))


def roofline_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of operations over
    the peak rate and bytes over the memory bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
