#!/usr/bin/env python3
"""Bring-up smoke: the HeteGen serving path on one chip, at full opt-1.3b.

    python3 chip_smoke.py

Runs in one process the path ``launch/serve.py --mode batch-offload
--paged`` runs — ``LLM`` -> ``ContinuousBatcher`` (paged) -> backend seam
-> ``PagedKVCache`` -> HeteGen offload — at opt-1.3b's published widths
and depth (24 layers, d_model 2048, 32 heads, d_ff 8192, vocab 50272, f32)
with weights drawn from a seed.  Three passes serve the same seeded greedy
prompts:

    resident     ResidentBackend: every weight in device memory
    offload-fp   HeteGenBackend(wstream="fp", budget_bytes=0): every linear
                 streams or splits between host and device
    offload-q8   the same with int8 + per-column scale streaming, served by
                 the paged batcher and again by the dense one

Checks: the resident and offload-fp passes pick the same first token for
every request, and their prefill logits agree within ``LOGIT_TOL``; both
offload passes moved weight bytes over the transfer stream; the q8 pass
gives the same tokens under both of its executors (docs/SERVING.md,
"Accuracy contract").  Any failed check or phase exits non-zero with no
result line.  The last line of a passing run is one JSON object naming
the device.  Times printed here are smoke output, not benchmark numbers.

The run refuses a device other than a TPU, and a TPU run whose kernels
would take the pure-jnp reference path instead of Pallas.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "opt-1.3b"
# one prompt past 128 tokens, none a multiple of 128: prefill runs q8_matmul
# with M = B*S that no 128-row block divides.  All within 2x of the first,
# so one prefill plan serves them (HeteGenBackend's retune hysteresis).
PROMPT_LENS = (200, 150, 101)
MAX_NEW = 8
SLOTS = 4                  # serve.py's batch-mode slot count
PAGE_SIZE = 16
SEED = 0
# Largest |prefill logit| difference allowed between offload-fp and
# resident.  The offload path computes each linear's host share in numpy
# f32 and its device share at the TPU's default matmul precision, while the
# resident path runs every column on the device, so the two round
# differently.  On seeded random opt-1.3b weights the logits have a
# standard deviation near 45 and the top-1 leads by far more than 4, while
# the rounding difference stays well under 4 (measured: PERF.md, Findings).
LOGIT_TOL = 4.0


class SmokeFailure(RuntimeError):
    """A phase or check of the smoke failed."""


class CompileClock:
    """Backend-compile seconds and persistent-cache hits and misses, summed
    from JAX's monitoring events while the clock is open."""

    def __init__(self):
        self.secs = 0.0
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()

    def reading(self):
        with self._lock:
            return self.secs, self.hits, self.misses

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.secs += duration

    def _on_event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def _tap_prefill(backend, sink: dict) -> None:
    """Record the logits of every prefill the batcher runs, keyed by the
    prompt row, without changing what the batcher sees."""
    import numpy as np
    inner = backend.prefill

    def prefill(batch, cache):
        cache, logits = inner(batch, cache)
        rows = np.asarray(batch["tokens"])
        for row, lg in zip(rows, np.asarray(logits, np.float32)):
            sink[tuple(int(t) for t in row)] = lg
        return cache, logits

    backend.prefill = prefill


def _serve(cfg, backend, prompts, *, paged: bool, trace: bool, log):
    """One batch-offload-shaped run through the LLM facade: submit every
    prompt, drain.  Returns (tokens per request, transfer-stream bytes)."""
    from repro.serving.api import LLM

    max_len = max(len(p) for p in prompts) + MAX_NEW + 8
    with LLM(cfg, backend=backend, own_backend=False, max_slots=SLOTS,
             max_len=max_len, paged=paged, page_size=PAGE_SIZE,
             trace=trace) as llm:
        rids = [llm.submit(p, MAX_NEW) for p in prompts]
        outs = llm.drain()
        st = llm.stats()
        moved = sum((s.attrs or {}).get("bytes", 0)
                    for s in llm.tracer.spans() if s.track == "transfer") \
            if trace else 0
    tokens = [outs[r].tokens for r in rids]
    log(f"  executor={st['executor']} paged={paged} "
        f"steps={st.get('steps')} tokens={tokens}")
    return tokens, moved


def _check_tokens(name, tokens, n_prompts, vocab):
    if len(tokens) != n_prompts:
        raise SmokeFailure(f"{name}: {len(tokens)} outputs for "
                           f"{n_prompts} requests")
    for t in tokens:
        if len(t) != MAX_NEW or not all(0 <= x < vocab for x in t):
            raise SmokeFailure(f"{name}: bad output {t}")


def run_smoke(arch: str = ARCH, platform: str = "tpu", *,
              logit_tol: float = LOGIT_TOL, log=print) -> dict:
    """Run the three passes and every check; returns what was measured.
    ``platform`` is the JAX platform the run must find.  Raises
    :class:`SmokeFailure` on any failed check."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.hw import describe_plan_hw, spec_for_device
    from repro.kernels import ops
    from repro.models import model as M
    from repro.serving.backends import HeteGenBackend, ResidentBackend

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"device: {json.dumps(device)}")
    if dev.platform != platform:
        raise SmokeFailure(f"expected a {platform} device, JAX found "
                           f"platform {dev.platform!r}")
    mode = ops._mode()
    log(f"kernel mode: {mode}")
    if platform == "tpu" and mode != "pallas":
        raise SmokeFailure(f"kernels would run in {mode!r} mode on the TPU, "
                           "not as Pallas kernels")
    hw = spec_for_device(dev)
    log(describe_plan_hw(hw, dev))

    cfg = get_config(arch)
    log(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"dtype={cfg.dtype} params={cfg.param_count() / 1e6:.1f}M")
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    rng = np.random.default_rng(SEED)
    prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)]
               for n in PROMPT_LENS]
    log(f"prompts: lengths={list(PROMPT_LENS)} max_new={MAX_NEW} "
        f"greedy slots={SLOTS} page_size={PAGE_SIZE}")

    results = {}

    def run_pass(name, make_backend, executors):
        c0, h0, m0 = clock.reading()
        t0 = time.perf_counter()
        log(f"pass {name}:")
        backend = make_backend()
        logits: dict = {}
        _tap_prefill(backend, logits)
        out = {"logits": logits}
        try:
            for paged in executors:
                toks, moved = _serve(cfg, backend, prompts, paged=paged,
                                     trace=hasattr(backend, "policies"),
                                     log=log)
                _check_tokens(name, toks, len(prompts), cfg.vocab_size)
                out["paged" if paged else "dense"] = toks
                out.setdefault("moved", moved)
            if hasattr(backend, "policies"):
                al = {ph: round(p.alpha, 4)
                      for ph, p in sorted(backend.policies.items())}
                log(f"  {describe_plan_hw(backend.hw, dev)} "
                    f"phase alphas={al} wstream={backend.wstream}")
                log(f"  transfer stream moved {out['moved']} bytes "
                    f"(moved={out['moved'] > 0})")
                if backend.hw is not hw:
                    raise SmokeFailure(f"{name} planned with {backend.hw.name}"
                                       f", not the device's {hw.name}")
        finally:
            backend.close()
            del backend
            gc.collect()
        c1, h1, m1 = clock.reading()
        out["compile_s"] = c1 - c0
        log(f"  compile: {out['compile_s']:.2f} s backend compile, "
            f"cache hits={h1 - h0} misses={m1 - m0}; "
            f"wall {time.perf_counter() - t0:.2f} s (smoke timing, "
            "not a benchmark)")
        results[name] = out

    with CompileClock() as clock:
        run_pass("resident", lambda: ResidentBackend(cfg, params), (True,))
        run_pass("offload-fp", lambda: HeteGenBackend(
            cfg, params, batch=SLOTS, budget_bytes=0, wstream="fp"), (True,))
        run_pass("offload-q8", lambda: HeteGenBackend(
            cfg, params, batch=SLOTS, budget_bytes=0, wstream="q8"),
            (True, False))

    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")

    res, fp, q8 = (results[k] for k in ("resident", "offload-fp",
                                        "offload-q8"))
    diffs, margins = [], []
    for p in prompts:
        key = tuple(p)
        if key not in res["logits"] or key not in fp["logits"]:
            raise SmokeFailure("a prompt's prefill logits were not recorded")
        a, b = res["logits"][key], fp["logits"][key]
        if not (np.isfinite(a).all() and np.isfinite(b).all()):
            raise SmokeFailure("non-finite prefill logits")
        diffs.append(float(np.abs(a - b).max()))
        top2 = np.sort(a)[-2:]
        margins.append(float(top2[1] - top2[0]))
    max_diff = max(diffs)
    first_agree = [r[0] == f[0] for r, f in zip(res["paged"], fp["paged"])]
    log(f"prefill logits offload-fp vs resident: max |diff| per request "
        f"{diffs}, overall {max_diff} (tolerance {logit_tol})")
    log(f"resident top-1 minus top-2 logit per request: {margins}")
    log(f"first tokens agree (resident vs offload-fp): {first_agree}; "
        f"all tokens agree: {res['paged'] == fp['paged']}")
    log(f"q8 tokens agree across executors (paged vs dense): "
        f"{q8['paged'] == q8['dense']}; q8 vs fp tokens agree: "
        f"{q8['paged'] == fp['paged']}")

    if not all(first_agree):
        raise SmokeFailure("first tokens differ between resident and "
                           f"offload-fp: {res['paged']} vs {fp['paged']}")
    if not max_diff <= logit_tol:
        raise SmokeFailure(f"prefill logits differ by {max_diff} > "
                           f"{logit_tol}")
    for name in ("offload-fp", "offload-q8"):
        if results[name]["moved"] <= 0:
            raise SmokeFailure(f"{name} moved no bytes over the transfer "
                               "stream")
    if q8["paged"] != q8["dense"]:
        raise SmokeFailure("q8 tokens differ across executors: "
                           f"{q8['paged']} vs {q8['dense']}")
    return {"device": device, "max_logit_diff": max_diff,
            "compile_s": {k: v["compile_s"] for k, v in results.items()}}


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    try:
        out = run_smoke(log=lambda s: print(s, flush=True))
    except SmokeFailure as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"total compile {sum(out['compile_s'].values()):.2f} s, wall "
          f"{time.perf_counter() - t0:.2f} s (smoke timing, not a benchmark)")
    print(json.dumps({"ok": True, "device": out["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
