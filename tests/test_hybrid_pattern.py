"""Block-pattern hybrids (Nemotron-H) through the normal serving path.

A tiny hybrid — pattern ``M*-M-`` (two Mamba-2 mixers, one attention
layer, two MLPs), d 64, 4 Mamba heads of 16, state 16, 2 groups, SSD
chunk 16 — runs through ``LLM`` -> paged ``ContinuousBatcher`` ->
``HeteGenBackend`` on seeded random weights and is compared with the
plain float32 reference (``tests/nemotron_h_reference.py``, which imports
nothing of the program), teacher-forced on what was served.

Tolerance: the program's logit rows must lie within ``LOGIT_TOL`` = 1e-3
of the reference's at every served position.  Both compute in float32;
they differ by rounding alone — the chunked SSD sums in another order
than the reference's one-token scan, the split linears add host and
device shares — which reads about 4e-6 here.  The same reference in
one-pass bfloat16 (its ``control``) lies about 4e-2 away, so a path that
lost precision, or dropped a term of the recurrence, fails by far.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import nemotron_h_reference as R
from repro.core.hw import PAPER_A10
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serving.api import LLM
from repro.serving.backends import (HeteGenBackend, ResidentBackend,
                                    ScanResidentBackend, enumerate_linears)
from repro.serving.batcher import ContinuousBatcher
from repro.serving.scheduler import RUNNING

LOGIT_TOL = 1e-3

CFG = ModelConfig(
    name="tiny-hybrid", family="hybrid", block_pattern="M*-M-", n_layers=5,
    d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
    vocab_size=512, attn_kind="gqa", pos_emb="none", mlp_kind="relu2",
    ssm_state=16, ssm_conv=4, ssm_expand=1, ssm_head_dim=16, ssm_chunk=16,
    ssm_groups=2, norm_eps=1e-5, tie_embeddings=False, dtype="float32",
    max_seq=512)

# the reference's configuration, under the published config.json's keys
CONF = {"hybrid_override_pattern": "M*-M-", "layer_norm_epsilon": 1e-5,
        "mamba_num_heads": 4, "mamba_head_dim": 16, "ssm_state_size": 16,
        "n_groups": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
        "attention_head_dim": 16}


@pytest.fixture(scope="module")
def hybrid():
    params = M.init_params(CFG, jax.random.PRNGKey(7))
    layers = []
    for kind, p in zip(CFG.block_pattern, params["layers"]):
        d = {"norm": p["norm"]["scale"]}
        d.update(p[{"M": "mixer", "*": "attn", "-": "mlp"}[kind]])
        layers.append(d)
    w = {"embed": params["embed"], "lm_head": params["lm_head"],
         "final_norm": params["final_norm"]["scale"], "layers": layers}
    return params, w


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _llm(params, *, alpha=None, **kw):
    be = HeteGenBackend(CFG, params, hw=PAPER_A10, budget_bytes=0,
                        alpha_override=alpha)
    opts = dict(max_slots=2, max_len=96, paged=True, page_size=16,
                chunk_tokens=24)
    opts.update(kw)
    llm = LLM(CFG, backend=be, own_backend=True, **opts)
    llm._ensure_batcher()
    return llm


def _tap_logits(llm):
    """Keep every logit row the batcher samples from, per request."""
    b = llm._batcher
    rows, inner = {}, b._sample_slot_rows

    def tap(logits, slots):
        for i, s in enumerate(slots):
            st = b.scheduler.slot_req[int(s)]
            if st is not None and st.status == RUNNING:
                rows.setdefault(st.rid, []).append(np.asarray(logits[i]))
        return inner(logits, slots)

    b._sample_slot_rows = tap
    return rows


def _worst_error(outs, rows, w):
    worst = 0.0
    for o in outs:
        seq = o.prompt + o.tokens[:-1]
        ref = np.asarray(R.logits(w, jnp.asarray(seq), conf=CONF))
        ref = ref[len(o.prompt) - 1:]
        got = np.stack(rows[o.rid])[:len(o.tokens)]
        assert got.shape == ref.shape
        worst = max(worst, float(np.abs(got - ref).max()))
    return worst


def _prompts(n_tokens, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, CFG.vocab_size, n)]
            for n in n_tokens]


@pytest.mark.parametrize("alpha", [0.0, 0.5, 1.0])
def test_serving_matches_reference(hybrid, alpha):
    """Prefill over several chunks (40 and 33 tokens at 24 a chunk), 22
    decode steps through the paged cache and the engine's split, and a
    third request on a reused slot: every served logit row matches the
    reference's full forward pass."""
    params, w = hybrid
    llm = _llm(params, alpha=alpha)
    eng = llm._batcher.backend.engine
    assert {p.alpha for p in eng.plan.values()} == {alpha}
    rows = _tap_logits(llm)
    outs = llm.generate(_prompts((40, 17, 33)), 22)
    assert all(len(o.tokens) == 22 for o in outs)
    assert _worst_error(outs, rows, w) < LOGIT_TOL
    # each admission zeroed its slot's state: 3 requests on 2 slots
    assert llm._batcher.kv.state_resets == 3
    assert llm._batcher.metrics.counter("serve.state_resets").value == 3
    llm.close()


def test_reused_slot_starts_from_zero_state(hybrid):
    """A slot's second occupant serves exactly what it serves alone: no
    conv or SSM state of the first occupant leaks into it."""
    params, _ = hybrid
    first, second = _prompts((30, 21), seed=3)
    llm = _llm(params, max_slots=1)
    outs = llm.generate([first, second], 12)
    llm.close()
    alone = _llm(params, max_slots=1)
    again = alone.generate([second, [1, 2, 3]], 12)
    alone.close()
    assert outs[1].tokens == again[0].tokens


def test_recompute_preemption_matches_reference(hybrid):
    """Too few pages for both sequences: the scheduler preempts one by
    recompute, and its resumed prefill of prompt + generated rebuilds the
    Mamba state exactly."""
    params, w = hybrid
    llm = _llm(params, n_pages=7, chunk_tokens=None)
    rows = _tap_logits(llm)
    outs = llm.generate(_prompts((40, 35), seed=5), 30)
    assert llm._batcher.scheduler.preemptions >= 1
    assert llm._batcher.scheduler.preempt_mode == "recompute"
    # a preempted request resamples nothing: keep its rows as served
    assert _worst_error(outs, rows, w) < LOGIT_TOL
    llm.close()


def test_resident_backend_matches_reference(hybrid):
    """The same layer math jitted end to end on device-resident weights."""
    params, w = hybrid
    be = ResidentBackend(CFG, params)
    llm = LLM(CFG, backend=be, max_slots=2, max_len=96, paged=True,
              page_size=16)
    llm._ensure_batcher()
    rows = _tap_logits(llm)
    outs = llm.generate(_prompts((20, 13), seed=9), 8)
    assert _worst_error(outs, rows, w) < LOGIT_TOL


@pytest.mark.parametrize("option", ["swap", "dedupe", "dense", "spec"])
def test_batcher_refuses_what_loses_state(hybrid, option):
    """Swap preemption (saves pages, not state), prefix dedupe (shares
    pages without the prefix's state), a dense cache and speculative
    rollback each raise instead of serving wrong tokens."""
    from repro.serving.speculative import NgramDrafter, SpecConfig

    params, _ = hybrid
    be = ResidentBackend(CFG, params)
    kw = {"swap": dict(paged=True, preempt_mode="swap"),
          "dedupe": dict(paged=True, prefix_dedupe=True),
          "dense": dict(paged=False),
          "spec": dict(paged=True, spec=SpecConfig(NgramDrafter(), k=2))
          }[option]
    with pytest.raises(NotImplementedError):
        ContinuousBatcher(CFG, backend=be, max_slots=2, max_len=64, **kw)


def test_other_paths_refuse_the_pattern(hybrid):
    params, _ = hybrid
    batch = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    with pytest.raises(NotImplementedError):
        M.forward_train(CFG, params, batch)
    with pytest.raises(NotImplementedError):
        ScanResidentBackend(CFG, params)
    with pytest.raises(NotImplementedError):
        M.init_cache(CFG, 1, 16)
    with pytest.raises(NotImplementedError):
        M.init_backend_cache(CFG, 1, 16)


def test_pattern_linears_and_counts():
    """One mixer a layer: Mamba in/out projections, attention q/k/v/o,
    MLP in/down, each in its own size group; the parameter count adds
    up the same widths."""
    specs = {s.name: s for s in enumerate_linears(CFG)}
    assert sorted(specs) == sorted(
        ["blk0.in_proj", "blk0.out_proj", "blk1.wq", "blk1.wk", "blk1.wv",
         "blk1.wo", "blk2.w_in", "blk2.w_down", "blk3.in_proj",
         "blk3.out_proj", "blk4.w_in", "blk4.w_down"])
    conv = 64 + 2 * 2 * 16
    assert (specs["blk0.in_proj"].n_in, specs["blk0.in_proj"].n_out) == \
        (64, 64 + conv + 4)
    assert specs["blk0.in_proj"].group == "ssm_in"
    assert specs["blk3.out_proj"].group == "ssm_out"
    assert specs["blk1.wk"].n_out == 2 * 16
    params = M.init_params(CFG, jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == CFG.param_count()


def test_published_widths():
    """The registered 98-layer model: 45 Mamba, 5 attention, 48 MLP
    layers; in_proj 8192 -> 37120, 47B parameters."""
    from repro.configs import get_config

    cfg = get_config("nemotron-h-47b")
    kinds = cfg.layer_kinds()
    assert (kinds.count("mamba"), kinds.count("attn"),
            kinds.count("mlp")) == (45, 5, 48)
    specs = {s.name: s for s in enumerate_linears(cfg)}
    assert specs["blk0.in_proj"].n_out == 37120
    assert specs["blk0.out_proj"].n_in == 16384
    assert 46e9 < cfg.param_count() < 48e9


def test_state_pools_in_the_paged_cache():
    """Pages for the attention layer only; one conv and one SSM state row
    per slot for each Mamba layer; a reset zeroes one row in place."""
    from repro.serving.kv_cache import PagedKVCache

    kv = PagedKVCache(CFG, 3, 64)
    cache = kv.init_cache()
    assert sorted(k for k in cache if k.startswith("pages_")) == \
        ["pages_k1", "pages_v1"]
    assert cache["state_conv0"].shape == (3, 3, 64 + 2 * 2 * 16)
    assert cache["state_ssm3"].shape == (3, 4, 16, 16)
    cache["state_ssm3"] = jnp.ones_like(cache["state_ssm3"])
    cache = kv.reset_state(cache, 1)
    ssm = np.asarray(cache["state_ssm3"])
    assert ssm[1].max() == 0 and ssm[0].min() == 1 and ssm[2].min() == 1
    assert kv.stats()["state_resets"] == 1


@pytest.mark.parametrize("which", ["conv", "scan"])
def test_state_step_kernels_match_reference(which):
    """The decode kernels (interpret mode) update only the named slots'
    rows, in place, as the plain gather/scatter reference does."""
    from repro.kernels import ref, ssm_step

    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    slots = jnp.asarray([3, 0, 4], jnp.int32)
    if which == "conv":
        pool = jax.random.normal(ks[0], (5, 3, 128))
        args = (jax.random.normal(ks[1], (3, 128)),
                jax.random.normal(ks[2], (4, 128)),
                jax.random.normal(ks[3], (128,)))
        got = ssm_step.conv_step(pool, slots, *args, interpret=True)
        want = ref.ssm_conv_step(pool, slots, *args)
    else:
        pool = jax.random.normal(ks[0], (5, 4, 16, 16))
        args = (jax.random.normal(ks[1], (3, 4, 16)),
                jax.nn.softplus(jax.random.normal(ks[2], (3, 4))),
                jax.random.normal(ks[3], (3, 4, 16)),
                jax.random.normal(ks[4], (3, 4, 16)),
                -jnp.arange(1.0, 5.0), jnp.full((4,), 0.5))
        got = ssm_step.scan_step(pool, slots, *args, interpret=True)
        want = ref.ssm_scan_step(pool, slots, *args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[1][1], pool[1])     # untouched row
