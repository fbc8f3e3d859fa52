"""The one traffic generator: seeds reorder a fixed set of sizes."""

import collections

import numpy as np
import pytest

from bench.harness import traffic
from bench.tests.conftest import CLOSED


def _sizes(reqs):
    return collections.Counter((len(r.prompt), r.max_new) for r in reqs)


@pytest.mark.parametrize("seed", [2**31 + 17, 7])
def test_same_seed_same_schedule(seed):
    a = traffic.make_requests(CLOSED, seed, 512)
    b = traffic.make_requests(CLOSED, seed, 512)
    assert [(r.prompt, r.max_new) for r in a] == \
        [(r.prompt, r.max_new) for r in b]


@pytest.mark.parametrize("seeds", [(1, 2), (2**31 + 3, 2**32 + 5)])
def test_seeds_share_sizes_in_another_order(seeds):
    a = traffic.make_requests(CLOSED, seeds[0], 512)
    b = traffic.make_requests(CLOSED, seeds[1], 512)
    assert _sizes(a) == _sizes(b)
    assert [r.max_new for r in a] != [r.max_new for r in b]


@pytest.mark.parametrize("seeds", [(1, 2), (2**31 + 3, 2**32 + 5)])
def test_a_fixed_order_sends_the_same_sizes_in_the_same_order(seeds):
    mix = dict(CLOSED, order="fixed")
    a = traffic.make_requests(mix, seeds[0], 512)
    b = traffic.make_requests(mix, seeds[1], 512)
    assert [(len(r.prompt), r.max_new) for r in a] == \
        [(len(r.prompt), r.max_new) for r in b]
    assert a[0].prompt != b[0].prompt
    assert _sizes(a) == _sizes(traffic.make_requests(CLOSED, 1, 512))


def test_every_deck_of_a_closed_loop_holds_the_deck():
    reqs = traffic.make_requests(CLOSED, 9, 512)
    n = CLOSED["deck"]
    decks = [_sizes(reqs[i:i + n]) for i in range(0, len(reqs), n)]
    assert len(decks) == CLOSED["decks"]
    assert all(d == decks[0] for d in decks)


@pytest.mark.parametrize("key", ["prompt", "output"])
def test_lengths_are_rounded_and_clipped(key):
    dist = dict(CLOSED[key], min=16, max=40, round_to=8)
    x = traffic.quantile_lengths(dist, 200)
    assert x.min() >= 16 and x.max() <= 40
    assert all(v % 8 == 0 for v in x)
    assert x.min() == 16 and x.max() == 40          # both clips bite


def test_quantile_lengths_follow_the_lognormal():
    dist = {"median": 300, "sigma": 0.8, "min": 1, "max": 10**6}
    x = traffic.quantile_lengths(dist, 1001)
    assert np.median(x) == 300
    assert np.log(x).std() == pytest.approx(0.8, rel=0.05)


def test_a_loop_the_generator_does_not_know_is_refused():
    with pytest.raises(ValueError):
        traffic.make_requests(dict(CLOSED, loop="open"), 1, 512)
    with pytest.raises(ValueError):
        traffic.make_requests(dict(CLOSED, order="random"), 1, 512)


def test_token_ids_come_from_the_seed():
    a = traffic.make_requests(CLOSED, 1, 512)
    b = traffic.make_requests(CLOSED, 2, 512)
    assert a[0].prompt != b[0].prompt
    assert all(0 <= t < 512 for r in a for t in r.prompt)


def test_warmup_lengths_cover_every_chunk_shape():
    got = traffic.warmup_prompt_lengths(CLOSED)
    assert got == [8, 16, 24, 32, 40, 48, 56, 64]
