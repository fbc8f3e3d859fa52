"""Serving launcher — the paper's deployment shape.

Every mode is flag parsing over ONE front door,
:class:`repro.serving.api.LLM` (docs/SERVING.md):

    resident       jitted one-shot generation, weights on device
    offload        HeteGen: weights in host memory, alpha-split linears,
                   pinned-ring streaming (`--budget-frac` sets the device
                   memory available for residency promotion); the backend
                   holds per-phase placement plans — compute-bound
                   prefill (alpha -> 1) and link-bound decode
    batch          continuous batching over N synthetic requests
    batch-offload  continuous batching over HeteGen-offloaded weights

The modes differ only in which backend is handed to the facade and
whether requests arrive together (one-shot executor) or staggered
(continuous batcher).  Scheduling is two more flags over the same door:
``--policy fcfs|priority|fair_share`` picks the admission/preemption
policy (with ``priority``, request i carries priority ``i %% 2`` so the
preemption path is actually exercised), ``--async`` serves through the
event-loop :class:`repro.serving.api.AsyncLLM` (no caller-driven
``step()``), and ``--n-pages`` shrinks the paged pool to provoke
optimistic-paging preemption.  ``--paged`` swaps the batch modes to the
paged KV cache; ``--sampler`` picks the per-request sampling (requests
carry their own :class:`repro.serving.sampling.SamplingParams`, so paged
and dense decode stay token-identical even stochastically); ``--stream``
prints the first request's tokens as they decode.  ``--spec
ngram|model`` turns on heterogeneous speculative decoding (CPU-side
drafting, batched GPU verification — docs/SERVING.md) with ``--spec-k``
draft tokens per step and ``--spec-adaptive`` per-request k control.

    PYTHONPATH=src python -m repro.launch.serve --arch opt-125m \\
        --mode offload --budget-frac 0.25 --requests 4
    PYTHONPATH=src python -m repro.launch.serve --mode batch --paged \\
        --policy priority --n-pages 24 --async

``--dryrun`` lowers/compiles the serve step for an assigned architecture
on the production mesh (delegates to :mod:`repro.launch.dryrun`).
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt-125m")
    ap.add_argument("--mode", choices=("resident", "offload", "batch",
                                       "batch-offload"),
                    default="offload")
    ap.add_argument("--budget-frac", type=float, default=0.0)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache for the batch modes")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--policy", choices=("fcfs", "priority", "fair_share"),
                    default="fcfs", help="scheduler admission/preemption "
                    "policy for the batch modes")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="serve through the event-loop AsyncLLM "
                    "(no caller-driven step())")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="shrink the paged pool to provoke preemption")
    ap.add_argument("--selfcheck", action="store_true",
                    help="paged-allocator runtime self-check: validate "
                    "free-list/ref-count/block-table invariants every "
                    "step and audit for leaked pages at close")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked prefill: admit long prompts at most "
                    "this many tokens per step so a long admission "
                    "cannot stall decode tenants")
    ap.add_argument("--no-prefix-dedupe", action="store_true",
                    help="disable admission-time page-aligned prompt "
                    "prefix sharing (paged mode only)")
    ap.add_argument("--spec", choices=("ngram", "model"), default=None,
                    help="speculative decoding: CPU-side drafting "
                    "(prompt-lookup ngrams or a draft model) with "
                    "batched verification on the target")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per speculative step")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="adapt k per request from acceptance history")
    ap.add_argument("--sampler", choices=("greedy", "temperature", "topk",
                                          "topp"), default="greedy")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--stream", action="store_true",
                    help="stream the first request token by token")
    ap.add_argument("--hw", default=None, help="hardware model for the "
                    "alpha law (a10 | v5e); default: the spec of the device "
                    "JAX runs on (core/hw.py DEVICE_KIND_SPECS)")
    ap.add_argument("--wstream", choices=("fp", "q8"), default="fp",
                    help="wire format of streamed weights in the offload "
                    "modes: fp streams shards as-is, q8 streams int8 + "
                    "per-column fp32 scales (~4x fewer link bytes; the "
                    "plan's alpha shifts toward the device, "
                    "docs/SERVING.md)")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="record zero-sync spans across the run and dump "
                    "a Chrome/Perfetto trace JSON (docs/OBSERVABILITY.md)")
    ap.add_argument("--overlap-report", action="store_true",
                    help="print the per-step I/O-hidden fraction, stream "
                    "utilization, and critical-path breakdown computed "
                    "from the recorded trace (implies tracing)")
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    args = ap.parse_args()

    if args.dryrun:
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", args.arch, "--shape", args.shape,
               "--mesh", args.mesh]
        raise SystemExit(subprocess.call(cmd))

    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.core.hw import HARDWARE, describe_plan_hw, spec_for_device
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import model as M
    from repro.serving.api import LLM
    from repro.serving.sampling import SamplingParams

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    if args.spec is not None:
        # repetitive prompts give the prompt-lookup drafter something to
        # look up (real text has this structure; random tokens do not)
        motif = [list(rng.integers(0, cfg.vocab_size, 4))
                 for _ in range(args.requests)]
        prompts = [(m * args.prompt_len)[:args.prompt_len] for m in motif]
    else:
        prompts = [list(rng.integers(0, cfg.vocab_size, args.prompt_len))
                   for _ in range(args.requests)]
    sampling = SamplingParams(
        kind=args.sampler, temperature=args.temperature,
        top_k=40 if args.sampler == "topk" else 0,
        top_p=0.9 if args.sampler == "topp" else 1.0)
    print(f"serving {cfg.name} ({cfg.param_count()/1e6:.1f}M) "
          f"mode={args.mode} sampler={args.sampler}")

    # the one divergence between modes: which backend the facade drives.
    # slots = the decode width the facade schedules, and therefore the
    # batch the offload plan is built for — matching them up front avoids
    # throwaway engine partitions (the batcher re-tunes to its slot count)
    slots = args.requests if args.mode == "offload" else 4
    backend = None
    if args.mode in ("offload", "batch-offload"):
        from repro.serving.backends import HeteGenBackend, enumerate_linears
        total = sum(s.nbytes for s in enumerate_linears(cfg))
        hw = spec_for_device() if args.hw is None else HARDWARE[args.hw]
        print(f"  {describe_plan_hw(hw)}")
        backend = HeteGenBackend(cfg, params, hw=hw,
                                 batch=slots,
                                 budget_bytes=args.budget_frac * total,
                                 wstream=args.wstream)
        if args.wstream == "q8":
            pol = backend.policy
            print(f"  wstream=q8: int8+scale wire format, "
                  f"decode alpha={pol.alpha:.3f}")

    spec = None
    if args.spec is not None:
        from repro.serving.speculative import (ModelDrafter, NgramDrafter,
                                               SpecConfig)
        drafter = NgramDrafter() if args.spec == "ngram" else \
            ModelDrafter(cfg, params,
                         max_len=args.prompt_len + args.max_new + 8)
        spec = SpecConfig(drafter=drafter, k=args.spec_k,
                          adaptive=args.spec_adaptive)

    tracing = bool(args.trace or args.overlap_report)
    llm_kw = dict(sampling=sampling, max_slots=slots,
                  max_len=args.prompt_len + args.max_new + 8,
                  paged=args.paged, page_size=args.page_size,
                  n_pages=args.n_pages, policy=args.policy,
                  chunk_tokens=args.chunk_tokens,
                  prefix_dedupe=False if args.no_prefix_dedupe else None,
                  spec=spec, selfcheck=args.selfcheck, trace=tracing)
    # give the priority policy something to schedule: alternate priorities
    prio = (lambda i: i % 2) if args.policy == "priority" else (lambda i: 0)

    facade = None
    if args.use_async:
        # the event loop owns the step() crank: submit/stream only
        from repro.serving.api import AsyncLLM
        with AsyncLLM(cfg, params, backend=backend, own_backend=True,
                      **llm_kw) as allm:
            facade = allm._llm
            if args.stream:
                for tok in allm.stream(prompts[0], args.max_new):
                    print(f"  stream> {tok}", flush=True)
                prompts = prompts[1:]
            handles = [allm.submit(p, args.max_new, priority=prio(i))
                       for i, p in enumerate(prompts)]
            outs = [h.result() for h in handles]
            st = allm.stats()
    else:
        with LLM(cfg, params, backend=backend, own_backend=True,
                 **llm_kw) as llm:
            facade = llm
            if args.stream:
                for tok in llm.stream(prompts[0], args.max_new):
                    print(f"  stream> {tok}", flush=True)
                prompts = prompts[1:]

            if args.mode in ("resident", "offload"):
                # requests arrive together: the facade runs them one-shot
                outs = llm.generate(prompts, args.max_new) \
                    if prompts else []
            else:
                # staggered arrivals: continuous batching
                for i, p in enumerate(prompts):
                    llm.submit(p, args.max_new, priority=prio(i))
                outs = list(llm.drain().values())
            st = llm.stats()

    total_toks = sum(len(o.tokens) for o in outs)
    print(f"{len(outs)} requests, {total_toks} tokens "
          f"via executor={st['executor']}, "
          f"{st.get('tokens_per_s', 0.0):.1f} tok/s")
    if "scheduler" in st:
        sc = st["scheduler"]
        print(f"scheduler: policy={sc['policy']} "
              f"preemptions={sc['preemptions']} "
              f"chunks={sc['chunks_planned']} "
              f"dedupe_hits={sc['dedupe_hits']} "
              f"(+{sc['dedupe_tokens']} tokens shared)")
    if "phase_alpha" in st:
        al = st["phase_alpha"]
        print("phase plans: " + "  ".join(
            f"{ph}: alpha={a:.3f}" for ph, a in sorted(al.items())))
        print(f"resident={st['resident_bytes']/1e6:.0f}MB")
    if "stream" in st:
        s = st["stream"]
        print(f"stream busy (s): cpu={s.cpu:.3f} pin={s.pin:.3f} "
              f"trans={s.trans:.3f} dev={s.dev:.3f}")
    if "paged" in st:
        pg = st["paged"]
        print(f"paged KV: page_size={pg['page_size']} "
              f"pool={pg['pool_pages']} pages, "
              f"{pg['mapped_pages']} still mapped")
    if "spec" in st:
        sp = st["spec"]
        print(f"speculative: drafter={args.spec} k={args.spec_k} "
              f"drafted={sp['drafted']} accepted={sp['accepted']} "
              f"rolled_back={sp['rolled_back']} "
              f"(acceptance {sp['acceptance_rate']:.2f})")
    if tracing:
        # the tracer's ring buffers are plain host memory — they outlive
        # the facade's close(), so export after teardown is safe
        if args.trace:
            doc = facade.write_trace(args.trace)
            print(f"trace: {args.trace} "
                  f"({len(doc['traceEvents'])} events)")
        if args.overlap_report:
            print(facade.overlap_report().render())


if __name__ == "__main__":
    main()
