#!/usr/bin/env bash
# One-step verify: tier-1 test suite + offload-runtime smoke.
#
#     bash tools/ci.sh
#
# Tier-1 is the ROADMAP's gating command; the smoke drives two decode steps
# through the HeteGen offload backend (tiny config) so the threaded engine
# path is exercised end to end outside pytest as well.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: static invariant lint (repro.analysis.lint) =="
# kernel index-map bounds, tile alignment, hot-path sync, PRNG and lock
# discipline; --strict also fails on bare (unjustified) suppressions.
# docs/ANALYSIS.md catalogs the rules.
python -m repro.analysis.lint --strict

echo "== tier-1: pytest =="
python -m pytest -x -q

echo "== smoke: offload runtime (tiny config, 2 decode steps) =="
python - <<'EOF'
import jax
import numpy as np

from repro.configs import get_config
from repro.core.hw import PAPER_A10
from repro.models import model as M
from repro.serving.offload_runtime import OffloadGenerator

cfg = get_config("tiny")
params = M.init_params(cfg, jax.random.PRNGKey(0))
off = OffloadGenerator(cfg, params, hw=PAPER_A10, budget_bytes=0, batch=2)
prompt = np.random.default_rng(0).integers(
    0, cfg.vocab_size, (2, 6)).astype(np.int32)
res = off.generate(prompt, 2)
assert res["tokens"].shape == (2, 2), res["tokens"].shape
assert res["batch"] == 2
off.close()
print("offload smoke OK:", res["tokens"].tolist(),
      f"alpha={res['alpha']:.3f}")
EOF

echo "== smoke: LLM facade (resident + offload, streaming request) =="
python - <<'EOF'
import jax
import numpy as np

from repro.configs import get_config
from repro.core.hw import PAPER_A10
from repro.models import model as M
from repro.serving.api import LLM
from repro.serving.backends import HeteGenBackend
from repro.serving.sampling import SamplingParams

cfg = get_config("tiny")
params = M.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [list(rng.integers(0, cfg.vocab_size, 6)) for _ in range(2)]

with LLM(cfg, params, max_slots=2, max_len=32, seed=0) as llm:
    outs = llm.generate(prompts, max_new=3)
    assert llm.last_executor == "generator", llm.last_executor
    streamed = list(llm.stream(
        prompts[0], max_new=3,
        sampling=SamplingParams(kind="topp", top_p=0.9, seed=1)))
    assert len(streamed) == 3, streamed

be = HeteGenBackend(cfg, params, hw=PAPER_A10, budget_bytes=0, batch=2)
with LLM(cfg, backend=be, own_backend=True, max_slots=2, max_len=32,
         seed=0) as off:
    got = off.generate(prompts, max_new=3)
    assert [o.tokens for o in got] == [o.tokens for o in outs]
    assert set(be.policies) == {"prefill", "decode"}, be.policies.keys()
assert be.engines == {}, "facade close must tear down the owned backend"
print("LLM facade smoke OK:", [o.tokens for o in outs], "stream:", streamed)
EOF

echo "== smoke: paged KV continuous batching over HeteGen (tiny config) =="
python - <<'EOF'
import jax
import numpy as np

from repro.configs import get_config
from repro.core.hw import PAPER_A10
from repro.models import model as M
from repro.serving.backends import HeteGenBackend, ResidentBackend
from repro.serving.batcher import ContinuousBatcher

cfg = get_config("tiny")
params = M.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (5, 3, 7)]

dense = ContinuousBatcher(cfg, backend=ResidentBackend(cfg, params),
                          max_slots=2, max_len=32)
dids = [dense.submit(p, 3) for p in prompts]
dout = dense.run_until_done()

hb = HeteGenBackend(cfg, params, hw=PAPER_A10, budget_bytes=0, batch=2)
b = ContinuousBatcher(cfg, backend=hb, max_slots=2, max_len=32,
                      paged=True, page_size=8, retune_hysteresis=1)
pids = [b.submit(p, 3) for p in prompts]
pout = b.run_until_done()
assert all(dout[d] == pout[p] for d, p in zip(dids, pids)), (dout, pout)
assert b.kv.free_pages == b.kv.n_pages - 1, "pages leaked"
hb.close()
print("paged smoke OK:", [pout[p] for p in pids])
EOF

echo "== smoke: scheduler policies + preemption + AsyncLLM (tiny config) =="
python - <<'EOF'
import jax
import numpy as np

from repro.configs import get_config
from repro.models import model as M
from repro.serving.api import AsyncLLM, LLM
from repro.serving.backends import ResidentBackend
from repro.serving.batcher import ContinuousBatcher

cfg = get_config("tiny")
params = M.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (6, 6, 5)]

# page-tight priority scheduling: the late high-priority request preempts
# a low-priority tenant (optimistic paging + host-swap resume) ...
b = ContinuousBatcher(cfg, backend=ResidentBackend(cfg, params),
                      own_backend=True, max_slots=2, max_len=32,
                      paged=True, page_size=8, n_pages=5, policy="priority")
lo = [b.submit(p, 12) for p in prompts[:2]]
for _ in range(3):
    b.step()
hi = b.submit(prompts[2], 3, priority=5)
done_order = []
while b.queue or b.active.any():
    b.step()
    done_order += [r.rid for r in b.requests.values()
                   if r.done and r.rid not in done_order]
out = {rid: r.generated for rid, r in b.requests.items()}
assert done_order[0] == hi, done_order
n_preempt = b.scheduler.preemptions
assert n_preempt >= 1
assert b.kv.free_pages == b.kv.usable_pages, "pages leaked"
b.close()

# ... and every request still matches its unpressured run token-for-token
ref = ContinuousBatcher(cfg, backend=ResidentBackend(cfg, params),
                        own_backend=True, max_slots=3, max_len=32)
for rid, (p, n) in zip(lo + [hi], [(prompts[0], 12), (prompts[1], 12),
                                   (prompts[2], 3)]):
    ref.submit(p, n, rid=rid)
assert ref.run_until_done() == out, "preempt/resume changed tokens"
ref.close()

# AsyncLLM: the event loop owns step(); stream() just yields
with LLM(cfg, params, max_slots=2, max_len=32, seed=0) as llm:
    want = [o.tokens for o in llm.generate([prompts[0]], max_new=4)]
with AsyncLLM(cfg, params, max_slots=2, max_len=32, seed=0) as allm:
    got = list(allm.stream(prompts[0], 4))
assert got == want[0], (got, want)
print("scheduler smoke OK: finish order", done_order,
      "preemptions", n_preempt, "async stream", got)
EOF

echo "== smoke: chunked prefill + paged-prefill kernel (tiny config) =="
python - <<'EOF'
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels import ops, paged_prefill, ref
from repro.models import model as M
from repro.serving.backends import ResidentBackend
from repro.serving.batcher import ContinuousBatcher

# paged-prefill Pallas kernel (interpret mode) vs the ref oracle at a
# mid-prompt kv_offset — the shape chunked admission runs every step
rng = np.random.default_rng(0)
b, hq, hkv, s, d, ps, t = 2, 4, 2, 32, 64, 8, 96
nb = t // ps
n_pages = 1 + b * nb
q = jnp.asarray(rng.standard_normal((b, hq, s, d)), jnp.float32)
kp = jnp.asarray(rng.standard_normal((n_pages, hkv, ps, d)), jnp.float32)
vp = jnp.asarray(rng.standard_normal((n_pages, hkv, ps, d)), jnp.float32)
bt = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(b, nb),
                 jnp.int32)
offs = jnp.full((b,), 40, jnp.int32)
want = ref.paged_prefill_attention(q, kp, vp, bt, offs)
got = paged_prefill.paged_prefill_attention(q, kp, vp, bt, offs,
                                            block_q=32, interpret=True)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           atol=2e-5, rtol=2e-5)

# chunked admission is token-identical to whole-shot (paged, greedy)
cfg = get_config("tiny")
params = M.init_params(cfg, jax.random.PRNGKey(0))
backend = ResidentBackend(cfg, params)
prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (13, 7)]

def run(chunk):
    bch = ContinuousBatcher(cfg, backend=backend, own_backend=False,
                            max_slots=2, max_len=32, paged=True,
                            page_size=8, chunk_tokens=chunk)
    rids = [bch.submit(p, 4) for p in prompts]
    out = bch.run_until_done()
    assert bch.kv.free_pages == bch.kv.usable_pages, "pages leaked"
    chunks = bch.scheduler.chunks_planned
    bch.close()
    return [out[r] for r in rids], chunks

whole, _ = run(None)
chunked, n_chunks = run(5)
backend.close()
assert chunked == whole, (chunked, whole)
assert n_chunks == sum(-(-len(p) // 5) for p in prompts if len(p) > 5)
print("chunked prefill smoke OK:", chunked, f"chunks={n_chunks}")
EOF

echo "== smoke: speculative decoding (CPU draft, batched verify) =="
python - <<'EOF'
import jax
import numpy as np

from repro.configs import get_config
from repro.core.hw import PAPER_A10
from repro.models import model as M
from repro.serving.backends import HeteGenBackend, ResidentBackend
from repro.serving.batcher import ContinuousBatcher
from repro.serving.speculative import NgramDrafter, SpecConfig

cfg = get_config("tiny")
params = M.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
# repetitive prompts: prompt-lookup drafting has something to look up
prompts = [([int(t) for t in rng.integers(1, cfg.vocab_size, 3)] * 5)[:12]
           for _ in range(2)]

base = ContinuousBatcher(cfg, backend=ResidentBackend(cfg, params),
                         own_backend=True, max_slots=2, max_len=48)
bids = [base.submit(p, 8) for p in prompts]
want = base.run_until_done()
base.close()

# greedy speculation over the paged offload path: token-identical, and
# the verify phase gets its own placement plan beside prefill/decode
hb = HeteGenBackend(cfg, params, hw=PAPER_A10, budget_bytes=0, batch=2)
b = ContinuousBatcher(cfg, backend=hb, max_slots=2, max_len=48,
                      paged=True, page_size=8,
                      spec=SpecConfig(drafter=NgramDrafter(), k=4))
sids = [b.submit(p, 8) for p in prompts]
got = b.run_until_done()
st = b.spec_stats
assert all(want[d] == got[s] for d, s in zip(bids, sids)), (want, got)
assert st.drafted > 0 and st.accepted > 0, st.as_dict()
assert st.drafted == st.accepted + st.rolled_back
assert b.kv.free_pages == b.kv.usable_pages, "pages leaked"
assert "verify" in hb.policies, hb.policies.keys()
hb.close()
b.close()
print("speculative smoke OK:", [got[s] for s in sids],
      f"acceptance={st.acceptance_rate:.2f}")
EOF

echo "== smoke: traced offload serving (opt-125m, chrome trace + overlap) =="
# full-width opt-125m, not tiny: tile-128 alpha quantization needs real
# output dims before the decode plan actually splits modules across the
# host/device streams, and the trace must show all of them
# (docs/OBSERVABILITY.md)
python - <<'EOF'
import jax
import numpy as np

from repro.configs import get_config
from repro.core.hw import PAPER_A10
from repro.models import model as M
from repro.serving.api import LLM
from repro.serving.backends import HeteGenBackend, enumerate_linears
from repro.telemetry import validate_chrome_trace

cfg = get_config("opt-125m")
params = M.init_params(cfg, jax.random.PRNGKey(0))
total = sum(s.nbytes for s in enumerate_linears(cfg))
be = HeteGenBackend(cfg, params, hw=PAPER_A10, batch=2,
                    budget_bytes=0.25 * total)
rng = np.random.default_rng(0)
prompts = [list(rng.integers(0, cfg.vocab_size, 8)) for _ in range(2)]

with LLM(cfg, params, backend=be, own_backend=True, max_slots=2,
         max_len=32, trace=True) as llm:
    for p in prompts:
        llm.submit(p, 4)
    outs = llm.drain()
    assert all(len(o.tokens) == 4 for o in outs.values()), outs
    doc = llm.write_trace("/tmp/hetegen_trace.json")
    rep = llm.overlap_report()
    snap = llm.metrics()

# chrome-trace schema + physical invariants: per-track non-overlap,
# monotone non-negative timestamps
problems = validate_chrome_trace(doc)
assert problems == [], problems[:5]
tracks = {e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
want = {"step", "phase", "pin", "transfer", "cpu_gemm", "device", "sample"}
assert want <= tracks, (want - tracks, tracks)

# the overlap report's headline number is a fraction
assert 0.0 <= rep.io_hidden_frac <= 1.0, rep.io_hidden_frac
assert rep.overall.io_busy > 0, "offload decode moved no bytes?"
assert rep.steps, "no per-step windows"

# the batcher's live instruments made it into the merged snapshot
assert snap["serve.tokens"] == 8.0, snap.get("serve.tokens")
assert snap["serve.steps"] >= 1, snap.get("serve.steps")
print(f"traced smoke OK: {len(doc['traceEvents'])} events, "
      f"tracks={sorted(tracks)}, io_hidden={rep.io_hidden_frac:.3f}")
EOF

echo "== smoke: q8 weight streaming (opt-125m, wire bytes + overlap) =="
# fp vs q8 wire format end to end (docs/ANALYSIS.md appendix): pin both
# runs to the same split (alpha_override) so the transfer streams move the
# same columns, then assert the q8 trace's wire bytes land at ~1/4 of fp
# (int8 payload + fp32 scales; <= 0.6x is the gate), that the *planned*
# alpha (pol.alpha — untouched by the override) strictly increases under
# compression, and that the q8 trace still yields a valid overlap report.
python - <<'EOF'
import jax
import numpy as np

from repro.configs import get_config
from repro.core.hw import PAPER_A10
from repro.models import model as M
from repro.serving.api import LLM
from repro.serving.backends import HeteGenBackend, enumerate_linears
from repro.telemetry import validate_chrome_trace

cfg = get_config("opt-125m")
params = M.init_params(cfg, jax.random.PRNGKey(0))
total = sum(s.nbytes for s in enumerate_linears(cfg))
rng = np.random.default_rng(0)
prompts = [list(rng.integers(0, cfg.vocab_size, 8)) for _ in range(2)]

wire, planned, outs = {}, {}, {}
for ws in ("fp", "q8"):
    be = HeteGenBackend(cfg, params, hw=PAPER_A10, batch=2,
                        budget_bytes=0.25 * total, wstream=ws,
                        alpha_override=0.5)
    with LLM(cfg, params, backend=be, own_backend=True, max_slots=2,
             max_len=32, trace=True) as llm:
        for p in prompts:
            llm.submit(p, 4)
        outs[ws] = llm.drain()
        planned[ws] = be.policies["decode"].alpha
        wire[ws] = sum((s.attrs or {}).get("bytes", 0)
                       for s in llm.tracer.spans()
                       if s.track == "transfer")
        if ws == "q8":
            doc = llm.write_trace("/tmp/hetegen_q8_trace.json")
            rep = llm.overlap_report()

assert all(len(o.tokens) == 4 for o in outs["q8"].values()), outs["q8"]
ratio = wire["q8"] / max(wire["fp"], 1)
assert ratio <= 0.6, (ratio, wire)
assert planned["q8"] > planned["fp"], planned
problems = validate_chrome_trace(doc)
assert problems == [], problems[:5]
assert 0.0 <= rep.overall.io_hidden_frac <= 1.0, rep.overall
assert rep.overall.io_busy > 0, "q8 decode moved no bytes?"
print(f"q8 streaming smoke OK: wire ratio={ratio:.3f} "
      f"(fp={wire['fp']} B, q8={wire['q8']} B), "
      f"planned alpha fp={planned['fp']:.3f} -> q8={planned['q8']:.3f}, "
      f"io_hidden={rep.overall.io_hidden_frac:.3f}")
EOF

echo "CI OK"
