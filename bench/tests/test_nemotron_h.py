"""The Nemotron-H configuration: its work counts at the published widths,
a tiny copy of it driven end to end on the CPU through the harness, and
the two readers of its Mamba layers on hand-built spans and trace."""

import json
import time
from types import SimpleNamespace as NS

import pytest

from bench.harness import check, spec
from bench.tests.conftest import BENCH, make_bench_dir

SEED = 2**31 + 41
CONF = json.loads((BENCH / "configs" /
                   "nemotron-h-47b-fp-offload.json").read_text())


def _arch():
    return spec.Bench().arch("nemotron_h")


def test_counts_at_published_widths():
    """Layers 16-20 of the published pattern, M * - M -, as integers."""
    c = _arch().counts(CONF)
    mamba = 8192 * (16384 + 20480 + 256) + 16384 * 8192
    attn = 2 * 8192 * 8192 + 2 * 8192 * 1024
    mlp = 2 * 8192 * 30720
    assert c == {
        "linear_params": 2 * mamba + attn + 2 * mlp,     # 2,034,237,440
        "head_params": 131072 * 8192,
        "paged_layers": 1,
        "kv_bytes_per_key": 2 * 8 * 128 * 4,
        "qo_bytes_per_row": 2 * 64 * 128 * 4,
        "attn_flops_per_key": 4 * 64 * 128,
        "ssm_layers": 2,
        # conv state 3 x 20480 and SSM state 256 x 64 x 256, f32, read
        # and written: 17.0 MB each way
        "ssm_state_bytes_per_row": 2 * 4 * (3 * 20480 + 256 * 64 * 256),
    }
    assert c["linear_params"] == 2_034_237_440
    assert all(type(v) is int for v in c.values())


def test_file_holds_the_published_config():
    """Every key of the release's config.json is in the file; the two
    that differ are the cut of depth, listed in ``reduced``."""
    pub = CONF["published"]
    assert CONF["reduced"] == ["num_hidden_layers",
                               "hybrid_override_pattern"]
    assert pub["num_hidden_layers"] == 98
    assert CONF["hybrid_override_pattern"] == \
        pub["hybrid_override_pattern"][16:21] == "M*-M-"
    assert (CONF["hidden_size"], CONF["mamba_num_heads"],
            CONF["mamba_head_dim"], CONF["ssm_state_size"],
            CONF["n_groups"], CONF["intermediate_size"]) == \
        (8192, 256, 64, 256, 8, 30720)


def test_program_config_is_the_published_block():
    cfg = _arch().program_config(CONF)
    assert (cfg.n_layers, cfg.block_pattern) == (5, "M*-M-")
    assert (cfg.d_inner, cfg.ssm_heads, cfg.hd) == (16384, 256, 128)


# a copy of the configuration at a tiny size, same keys
TINY = dict(CONF, name="tiny-nemoh", hidden_size=64, mamba_num_heads=4,
            mamba_head_dim=16, expand=1, ssm_state_size=16, n_groups=2,
            chunk_size=16, num_attention_heads=4, num_key_value_heads=2,
            attention_head_dim=16, intermediate_size=128, vocab_size=512,
            max_position_embeddings=256)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory, jax_restored):
    root = make_bench_dir(tmp_path_factory.mktemp("bench"),
                          cells={"nemoh.closed": ("tiny-nemoh",
                                                  "tiny-closed")},
                          configs={"tiny-nemoh": TINY})
    return spec.Bench(root=root, bench_dir=root / "bench")


def test_tiny_hybrid_is_correct_and_its_control_is_not(tiny):
    """Prompts up to 64 tokens at 32 a chunk carry state across chunks;
    three slots are reused by every later request."""
    from bench.harness.cell import execute

    out = execute(tiny, "nemoh.closed", SEED, 3.0, False,
                  t_start=time.perf_counter(), log=lambda s: None,
                  require_tpu=False, control=True)
    assert out["correct"], out["checks"]
    limits = {k: v["limit"] for k, v in out["checks"].items()}
    assert not check.judge(out["control"], limits), (out["checks"],
                                                     out["control"])
    assert out["metrics"]["itl_p50_ms"]["value"] > 0


def test_reference_keeps_host_weights_on_the_device():
    """The first call moves the host arrays it multiplies (linears and a
    host-held head) onto the device, in ``w``; the embedding stays where
    it is held, and a second call reads the same logits."""
    import jax
    import numpy as np

    conf = dict(TINY, hybrid_override_pattern="M*-", num_hidden_layers=3)
    w = _arch().make_weights(conf)(jax.random.PRNGKey(3))
    w["lm_head"] = np.asarray(w["lm_head"])
    ref = spec.Bench().reference("nemotron_h_reference")
    toks = jax.numpy.arange(24) % conf["vocab_size"]
    first = np.asarray(ref.logits(w, toks, conf=conf))
    assert isinstance(w["embed"], np.ndarray)
    assert not any(isinstance(v, np.ndarray) for v in
                   [w["lm_head"]] + [v for p in w["layers"]
                                     for v in p.values()])
    np.testing.assert_array_equal(np.asarray(ref.logits(w, toks,
                                                        conf=conf)), first)


def _sp(name, track, t0, t1, **attrs):
    return NS(name=name, track=track, t0=t0, t1=t1, attrs=attrs or None)


def _step_share(spans):
    ctx = NS(spans=spans, w0=0.0, w1=10.0)
    return spec.Bench().metric_reader("ssm.step_share")(ctx)


def test_step_share_reads_ssm_spans_inside_decode_steps():
    """Two decode steps of 1 s each; 0.3 s of ssm spans inside them (one
    span overlapping another counts once), a prefill step's scan and a
    span outside any step do not count."""
    spans = [
        _sp("step1", "step", 1.0, 2.0, phase="decode"),
        _sp("step2", "step", 3.0, 4.0, phase="decode"),
        _sp("step3", "step", 5.0, 6.0, phase="prefill"),
        _sp("state_step", "ssm", 1.1, 1.2, rows=16),
        _sp("state_step", "ssm", 1.15, 1.25, rows=16),
        _sp("state_step", "ssm", 3.5, 3.65, rows=16),
        _sp("scan", "ssm", 5.1, 5.5, rows=1, tokens=128),
        _sp("state_step", "ssm", 2.5, 2.6, rows=16),
    ]
    assert _step_share(spans) == pytest.approx(100.0 * 0.3 / 2.0)


@pytest.mark.parametrize("spans", [
    [],
    [_sp("step1", "step", 1.0, 2.0, phase="decode")],
    [_sp("state_step", "ssm", 1.1, 1.2)],
], ids=["nothing", "no-ssm-track", "no-decode-step"])
def test_step_share_reads_nothing_without_both(spans):
    assert _step_share(spans) is None


def _roofline(ops, decode, counts):
    """The reader on a hand-built trace: ``ops`` (name, start, seconds)
    on one chip; ``decode`` the harness's (t0, t1, rows, lens) records."""
    from bench.harness import trace as trace_lib

    tr = {"chips": {"/device:TPU:0": [[n, t0, d, ""] for n, t0, d in ops]},
          "host": [], "lines": {}}
    ctx = NS(counts=counts, peaks={"bf16_flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9},
             layer=NS(decode=decode), in_window=lambda t: 0.0 <= t <= 10.0,
             kernel_time=lambda p: trace_lib.kernel_time(tr, 0.0, 10.0, p))
    return spec.Bench().metric_reader("ssm_decode_roofline")(ctx)


def test_roofline_counts_the_state_bytes_of_the_window_rows():
    """Two decode calls of 16 rows in the window (one past the close does
    not count), two SSM layers: 64 row-layers of 34,045,952 bytes in
    4 ms of the two kernels' time, and no other op counted."""
    counts = _arch().counts(CONF)
    ops = [("%ssm_state_conv.3 = f32[16,20480]", 1.0, 0.0002),
           ("%ssm_state_scan.7 = (f32[16,256,64]", 1.001, 0.0018),
           ("%ssm_state_conv = f32[16,20480]", 2.0, 0.0002),
           ("%ssm_state_scan = (f32[16,256,64]", 2.001, 0.0018),
           ("%_paged.1 = f32[1024,1,128]", 2.01, 0.5),
           ("%fusion.2 = f32[16,1,16384]", 2.02, 0.5)]
    decode = [(1.0, 1.5, 16, []), (2.0, 2.5, 16, []), (11.0, 11.5, 16, [])]
    got = _roofline(ops, decode, counts)
    want = 100.0 * (32 * 2 * 34_045_952 / 819e9) / 0.004
    assert got == pytest.approx(want)
    assert got < 100.0


def test_roofline_reads_nothing_without_kernels_or_counts():
    counts = _arch().counts(CONF)
    assert _roofline([("%fusion = f32[16]", 1.0, 0.1)],
                     [(1.0, 1.5, 16, [])], counts) is None
    opt = spec.Bench().arch("opt").counts(
        spec.Bench().config("opt-13b-fp-offload"))
    assert _roofline([("%ssm_state_scan = f32[1]", 1.0, 0.1)],
                     [(1.0, 1.5, 16, [])], opt) is None
