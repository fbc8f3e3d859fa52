"""Operations and bytes of the work the program does, from a
configuration's counts.

Each architecture module's ``counts(conf)`` turns sizes into per-token
numbers (``bench/configs/<arch>_arch.py``); this module multiplies them
by the shapes a run recorded.  Both are kept with the benchmark so that
no change to the program can change how its work is counted.  Counts are
what the algorithm needs, never more: a roofline share computed from
them cannot pass 100% unless a time leaves out part of the work.
"""

from __future__ import annotations

from typing import Dict, Iterable


def model_flops(counts: Dict, tokens: int, head_rows: int) -> float:
    """Model FLOPs of ``tokens`` positions through every layer's linears,
    plus ``head_rows`` rows through the output head (2 per multiply-add).
    Attention scores are left out, so this never counts more than the
    program did."""
    return 2.0 * tokens * counts["linear_params"] \
        + 2.0 * head_rows * counts["head_params"]


def paged_decode_bytes(counts: Dict, kv_lens: Iterable[int]) -> float:
    """Bytes one paged decode-attention call (one layer) must read and
    write: each row's keys and values up to its length, its query and its
    output."""
    rows = list(kv_lens)
    return float(sum(n * counts["kv_bytes_per_key"] for n in rows)
                 + len(rows) * counts["qo_bytes_per_row"])


def paged_decode_flops(counts: Dict, kv_lens: Iterable[int]) -> float:
    """Scores and weighted sum of one call, each row up to its length."""
    return float(sum(n * counts["attn_flops_per_key"] for n in kv_lens))


def roofline_seconds(flops: float, nbytes: float, peaks: Dict) -> float:
    """The least time the chip could take: the larger of operations over
    the peak rate and bytes over the memory bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
