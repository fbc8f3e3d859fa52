"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``traffic/<name>.json``) states the loop and its load, and the
distributions of prompt and output lengths:

    loop          "closed": ``clients`` callers, each sending its next
                  request when the last one finished
    clients       how many callers
    slots         decode slots of the server
    chunk_tokens  the server's prefill chunk
    prompt        {"median", "sigma", "min", "max", "round_to"}: lognormal,
                  rounded up to a multiple of ``round_to``, clipped
    output        the same for the number of tokens each request makes
    deck          how many requests one deck holds
    decks         how many decks one run may draw
    deck_seed     seed that pairs prompt with output lengths in the deck
    order         "seed" (the default): each deck shuffled by the run's
                  ``--seed``; "fixed": shuffled by ``deck_seed`` alone, so
                  every seed sends the same sizes in the same order

Every seed does the same work.  The lengths are not drawn at random but
taken at evenly spaced quantiles of their distributions, so each deck
holds the same sizes; ``deck_seed`` pairs them.  The clients send deck
after deck: every run of ``clients`` requests in a row holds the deck's
sizes when ``deck == clients``.  The seed draws the prompts' token ids,
and with ``order`` "seed" the order of each deck.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Request:
    """One request of a run: its prompt and how many tokens it makes."""

    index: int
    prompt: List[int]
    max_new: int


def quantile_lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lognormal lengths at the quantiles (i + 1/2) / n, rounded up
    to ``round_to`` and clipped to [min, max]."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    r = int(dist.get("round_to", 1))
    x = np.ceil(x / r) * r
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def deck(mix: Dict, n: int) -> List[tuple]:
    """The (prompt length, output length) pairs of one deck of ``n``."""
    p = quantile_lengths(mix["prompt"], n)
    o = quantile_lengths(mix["output"], n)
    pair = np.random.default_rng(int(mix["deck_seed"])).permutation(n)
    return [(int(p[i]), int(o[j])) for i, j in enumerate(pair)]


def make_requests(mix: Dict, seed: int, vocab: int) -> List[Request]:
    """The run's requests, in the order the clients send them."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rng = np.random.default_rng(int(seed))
    order = mix.get("order", "seed")
    if order not in ("seed", "fixed"):
        raise ValueError(f"unknown order {order!r}")
    shuffle = rng if order == "seed" else \
        np.random.default_rng([int(mix["deck_seed"]), 1])
    n = int(mix["deck"])
    base = deck(mix, n)
    sizes = []
    for _ in range(int(mix["decks"])):
        sizes += [base[i] for i in shuffle.permutation(n)]
    return [Request(i, [int(t) for t in rng.integers(0, vocab, p)], int(o))
            for i, (p, o) in enumerate(sizes)]


def warmup_prompt_lengths(mix: Dict) -> List[int]:
    """Prompt lengths that make the server run every prefill shape this
    mix can produce: each multiple of ``round_to`` up to the chunk, alone
    and as the tail of a chunked prompt."""
    r = int(mix["prompt"].get("round_to", 1))
    chunk = int(mix["chunk_tokens"])
    lo = int(mix["prompt"]["min"])
    short = [s for s in range(r, chunk + 1, r) if s >= lo]
    longer = [chunk + s for s in range(r, chunk + 1, r)
              if chunk + s <= mix["prompt"]["max"]]
    return short + longer

