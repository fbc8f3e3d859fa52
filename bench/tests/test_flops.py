"""Operations and bytes, against numbers worked out by hand for OPT-13B,
through the OPT module's counts."""

import pytest

from bench.harness import flops, spec

OPT13B = {"num_hidden_layers": 4, "hidden_size": 5120, "ffn_dim": 20480,
          "vocab_size": 50272, "dtype": "float32"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def counts():
    return spec.Bench().arch("opt").counts


def test_linear_params_of_one_opt13b_layer(counts):
    # q, k, v, o: 4 * 5120^2 = 104,857,600; in and down: 2 * 5120 * 20480
    # = 209,715,200
    one = counts(dict(OPT13B, num_hidden_layers=1))
    assert one["linear_params"] == 314_572_800
    assert counts(OPT13B)["linear_params"] == 4 * 314_572_800


def test_model_flops_of_one_decode_row(counts):
    # 2 FLOPs per weight per token through 4 layers, plus the tied head
    # 2 * 5120 * 50272 for the one row whose logits are computed
    want = 2 * 4 * 314_572_800 + 2 * 5120 * 50272
    assert flops.model_flops(counts(OPT13B), 1, 1) == want == 3_031_367_680


def test_model_flops_of_a_prefill_chunk_counts_one_head_row(counts):
    got = flops.model_flops(counts(OPT13B), 256, 1)
    assert got == 256 * 2 * 4 * 314_572_800 + 2 * 5120 * 50272


def test_paged_decode_bytes_and_flops(counts):
    # one row of 100 keys: K and V are 100 * 5120 f32 each (4,096,000
    # bytes), plus the query and the output row (2 * 5120 * 4)
    c = counts(OPT13B)
    assert c["paged_layers"] == 4
    assert flops.paged_decode_bytes(c, [100]) == 4_096_000 + 40_960
    assert flops.paged_decode_flops(c, [100]) == 4 * 100 * 5120


def test_paged_decode_counts_each_row_at_its_own_length(counts):
    # a batch of rows reads each row's keys and values up to its own length
    c = counts(OPT13B)
    lens = [100, 1, 2048]
    assert flops.paged_decode_bytes(c, lens) == sum(
        flops.paged_decode_bytes(c, [n]) for n in lens)
    assert flops.paged_decode_flops(c, lens) == 4 * 2149 * 5120


@pytest.mark.parametrize("fl,nb,want", [
    (197e12, 1.0, 1.0),          # compute bound: one second at peak
    (1.0, 819e9, 1.0),           # bandwidth bound: one second at peak
    (197e12, 2 * 819e9, 2.0),    # the larger of the two bounds
])
def test_roofline_seconds(fl, nb, want):
    assert flops.roofline_seconds(fl, nb, PEAKS) == pytest.approx(want)
