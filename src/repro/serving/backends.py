"""Pluggable linear backends — one layer-math core, many executions.

The decoder math for the dense GQA families is written once
(:func:`repro.models.model.decoder_layer` /
:func:`repro.models.model.backend_prefill`) with every weight matmul routed
through an injected ``linear(x, name)`` callable.  This module provides the
two concrete executions of that seam:

    ResidentBackend   weights live in accelerator memory; the whole forward
                      is jitted (prefill/decode compiled once per shape,
                      decode cache donated) — the production resident path.
    HeteGenBackend    weights live in host memory; linears execute through
                      :class:`repro.core.engine.HeteGenEngine` under a
                      batch-aware placement plan (resident / alpha-split /
                      streamed), eagerly layer by layer, exactly how
                      offloading runtimes run.

Both expose the same driver surface — ``init_cache`` / ``prefill`` /
``decode`` / ``linear`` — so :class:`repro.serving.engine.Generator` and
:class:`repro.serving.batcher.ContinuousBatcher` schedule over either one
interchangeably, and their outputs match to fp tolerance
(tests/test_backends.py).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.alpha import resolve_phase_tokens
from repro.core.engine import HeteGenEngine, ModulePlan, StreamStats
from repro.core.hw import HardwareSpec, spec_for_device
from repro.core.policy import LinearSpec, PolicyResult, build_policy
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serving.kv_cache import PagedKVCache
from repro.telemetry.recalibrate import recalibrate_alpha
from repro.telemetry.tracer import NULL_TRACER, Tracer


@runtime_checkable
class LinearBackend(Protocol):
    """The backend seam: everything the shared layer math needs.

    ``linear(x, name)`` computes ``x @ W[name]`` with bias applied, for the
    flat linear names produced by :func:`enumerate_linears`
    ("blk{l}.wq", "blk{l}.w_down", ...).  ``cache_batch_axis`` is the axis
    carrying the batch in every cache buffer (the continuous batcher's
    slot-merge axis).

    The serving **phase** is part of the seam: ``prefill`` and ``decode``
    are distinct entry points because their placement economics differ
    (paper §4.1 — prefill is compute-bound, decode link-bound), and a
    planning backend may execute them under different plans.  Backends
    that re-plan expose ``retune(batch, phase=..., tokens_per_seq=...)``;
    schedulers probe for it with ``hasattr`` (resident backends don't
    plan, so it is not part of the required protocol).  Backends with a
    staging pipeline may likewise expose ``prefetch_next_step()`` — the
    executor calls it between a decode step's math and its host-side
    sampling so step N+1's weight pins overlap step N's tail.

    Backends may also expose ``verify(batch, cache)`` — a prefill-shaped
    step that returns logits for **all** positions (B, S, V) instead of
    just the last, the scoring pass of speculative decoding.  The batcher
    probes for it with ``hasattr``; backends without it cannot serve
    speculative requests.
    """

    cache_batch_axis: int

    def linear(self, x: jax.Array, name: str) -> jax.Array: ...

    def init_cache(self, batch: int, max_len: int) -> Dict: ...

    def init_paged_cache(self, batch: int, max_len: int, *,
                         page_size: int = 16,
                         n_pages: Optional[int] = None,
                         kv_dtype: Optional[str] = None,
                         check: bool = False
                         ) -> "PagedKVCache": ...

    def prefill(self, batch: Dict, cache: Dict
                ) -> Tuple[Dict, jax.Array]: ...

    def decode(self, token: jax.Array, cache: Dict
               ) -> Tuple[Dict, jax.Array]: ...

    def close(self) -> None: ...


def enumerate_linears(cfg: ModelConfig,
                      wstream: str = "fp") -> List[LinearSpec]:
    """The model's offloadable linears with size groups (paper §4.3).

    ``wstream`` stamps the streamed wire format on every spec so the
    policy layer prices the link in wire bytes (``LinearSpec.wire_bytes``)
    while compute stays in fp bytes."""
    by = cfg.dtype_bytes()
    hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    d, f = cfg.d_model, cfg.d_ff
    ws = wstream

    def spec(name, n_in, n_out, group):
        return LinearSpec(name, n_in, n_out, group, by, wire=ws)

    out = []
    for l in range(cfg.n_layers):
        out += [
            spec(f"blk{l}.wq", d, hq * hd, "attn"),
            spec(f"blk{l}.wk", d, hkv * hd, "attn_kv"),
            spec(f"blk{l}.wv", d, hkv * hd, "attn_kv"),
            spec(f"blk{l}.wo", hq * hd, d, "attn"),
        ]
        if cfg.mlp_kind.startswith("gated"):
            out += [spec(f"blk{l}.w_gate", d, f, "mlp"),
                    spec(f"blk{l}.w_up", d, f, "mlp"),
                    spec(f"blk{l}.w_down", f, d, "mlp_down")]
        else:
            out += [spec(f"blk{l}.w_in", d, f, "mlp"),
                    spec(f"blk{l}.w_down", f, d, "mlp_down")]
    return out


def _np(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))


class ResidentBackend:
    """Device-resident weights; the shared forward jitted end to end.

    Construction materializes an unstacked copy of every linear (jax
    indexing copies, it does not view), so a caller that also keeps the
    stacked ``params`` tree alive holds ~2x the weight bytes on the
    device — drop the stacked tree after construction when serving large
    models through this backend.
    """

    cache_batch_axis = 0

    def __init__(self, cfg: ModelConfig, params: Dict):
        self.cfg = cfg
        shared, weights, biases = M.extract_backend_params(cfg, params)
        self.shared = shared
        self.weights = {k: jnp.asarray(v) for k, v in weights.items()}
        self.biases = {k: jnp.asarray(v) for k, v in biases.items()}

        def _linear_from(weights, biases):
            def lin(x, name):
                y = x @ weights[name]
                b = biases.get(name)
                return y if b is None else y + b
            return lin

        self._lin = _linear_from(self.weights, self.biases)

        def _prefill(shared, weights, biases, batch, cache):
            return M.backend_prefill(cfg, shared, batch, cache,
                                     linear=_linear_from(weights, biases))

        def _decode(shared, weights, biases, token, cache):
            return M.backend_decode(cfg, shared, token, cache,
                                    linear=_linear_from(weights, biases))

        def _verify(shared, weights, biases, batch, cache):
            return M.backend_prefill(cfg, shared, batch, cache,
                                     linear=_linear_from(weights, biases),
                                     all_logits=True)

        # the cache is donated in ALL steps: callers never reuse the
        # input cache, and for paged admission donation lets the page
        # pools update in place instead of copying every pool per admit
        self._prefill = jax.jit(_prefill, donate_argnums=(4,))
        self._decode = jax.jit(_decode, donate_argnums=(4,))
        self._verify = jax.jit(_verify, donate_argnums=(4,))

    # -- LinearBackend surface -----------------------------------------
    def linear(self, x: jax.Array, name: str) -> jax.Array:
        return self._lin(x, name)

    def init_cache(self, batch: int, max_len: int) -> Dict:
        return M.init_backend_cache(self.cfg, batch, max_len)

    def init_paged_cache(self, batch: int, max_len: int, *,
                         page_size: int = 16,
                         n_pages: Optional[int] = None,
                         kv_dtype: Optional[str] = None,
                         check: bool = False) -> PagedKVCache:
        return PagedKVCache(self.cfg, batch, max_len, page_size=page_size,
                            n_pages=n_pages, kv_dtype=kv_dtype, check=check)

    def prefill(self, batch: Dict, cache: Dict) -> Tuple[Dict, jax.Array]:
        return self._prefill(self.shared, self.weights, self.biases,
                             batch, cache)

    def decode(self, token: jax.Array, cache: Dict
               ) -> Tuple[Dict, jax.Array]:
        return self._decode(self.shared, self.weights, self.biases,
                            token, cache)

    def verify(self, batch: Dict, cache: Dict) -> Tuple[Dict, jax.Array]:
        """Score all positions of a draft run: (B, S) tokens in, logits
        (B, S, V) out — one prefill-shaped step replaces S decode steps."""
        return self._verify(self.shared, self.weights, self.biases,
                            batch, cache)

    def close(self) -> None:
        pass


class ScanResidentBackend:
    """The scan-stacked resident path behind the backend driver surface.

    Wraps ``M.prefill`` / ``M.decode_step`` over the stacked params — the
    compiled trunk the :class:`repro.serving.engine.Generator` runs by
    default.  Unlike :class:`ResidentBackend` it supports every transformer
    family (MLA, MoE, int8 KV, encdec), but its per-linear execution is not
    pluggable; the batch axis of its cache leaves is 1 (stack-major).
    """

    cache_batch_axis = 1

    def __init__(self, cfg: ModelConfig, params: Dict):
        self.cfg = cfg
        self.params = params

        def _prefill(params, batch, cache):
            return M.prefill(cfg, params, batch, cache)

        def _decode(params, token, cache):
            return M.decode_step(cfg, params, token, cache)

        def _verify(params, batch, cache):
            return M.prefill(cfg, params, batch, cache, all_logits=True)

        self._prefill_fn = jax.jit(_prefill)
        self._decode_fn = jax.jit(_decode, donate_argnums=(2,))
        self._verify_fn = jax.jit(_verify, donate_argnums=(2,))

    def init_cache(self, batch: int, max_len: int) -> Dict:
        return M.init_cache(self.cfg, batch, max_len)

    def init_paged_cache(self, batch: int, max_len: int, **kw):
        raise NotImplementedError(
            "the scan-stacked cache is not pageable; use ResidentBackend "
            "or HeteGenBackend for paged serving")

    def prefill(self, batch: Dict, cache: Dict) -> Tuple[Dict, jax.Array]:
        return self._prefill_fn(self.params, batch, cache)

    def decode(self, token: jax.Array, cache: Dict
               ) -> Tuple[Dict, jax.Array]:
        return self._decode_fn(self.params, token, cache)

    def verify(self, batch: Dict, cache: Dict) -> Tuple[Dict, jax.Array]:
        return self._verify_fn(self.params, batch, cache)

    def close(self) -> None:
        pass


class HeteGenBackend:
    """HeteGen-scheduled offloaded execution of the shared layer math.

    Weights live in host memory; every ``linear`` runs through a threaded
    :class:`HeteGenEngine` under a placement plan built for the *real*
    workload — §4.1's cost model shifts the optimal alpha with compute
    intensity, so ``retune(batch, phase=...)`` rebuilds the plan (and the
    engine's weight partition) whenever the serving batch changes.

    The backend is **phase-aware** (docs/SERVING.md): it holds one plan
    and one engine partition per serving phase.  Decode moves every weight
    byte to produce ``batch`` tokens (link/host bound — small alpha, the
    host GEMM earns its keep), while prefill computes ``batch * prompt``
    positions against the same traffic (compute bound — alpha -> 1, stream
    nearly everything to the accelerator).  ``prefill``/``decode`` route
    their linears through their own phase's partition; the prefill plan is
    (re)tuned lazily from the observed prompt shape, with a multiplicative
    hysteresis (``prefill_retune_factor``) so prompt-length jitter does
    not rebuild the engine.  Engines share device-resident module copies
    through a common ``resident_store``, so dual plans never duplicate
    promoted weights on the accelerator.
    """

    cache_batch_axis = 0

    def __init__(self, cfg: ModelConfig, params: Dict, *,
                 hw: Optional[HardwareSpec] = None,
                 budget_bytes: Optional[float] = None,
                 batch: int = 1,
                 use_alpha_benchmark: bool = True,
                 use_module_scheduler: bool = True,
                 alpha_override: Optional[float] = None,
                 phase_plans: bool = True,
                 prefill_retune_factor: float = 2.0,
                 tracer: Tracer = NULL_TRACER,
                 recalibrate: Optional[float] = None,
                 recalibrate_every: int = 16,
                 wstream: str = "fp"):
        if wstream not in ("fp", "q8"):
            raise ValueError(f"unknown wire format {wstream!r} "
                             "(expected 'fp' or 'q8')")
        self.cfg = cfg
        shared, weights, biases = M.extract_backend_params(cfg, params)
        self.shared = shared
        self._host_weights = {k: _np(v) for k, v in weights.items()}
        self._host_biases = {k: _np(v) for k, v in biases.items()}
        self._ops = M.make_backend_ops(cfg)   # jitted norms/attention/head
        self.wstream = wstream
        self.linears = enumerate_linears(cfg, wstream=wstream)
        # the planning rig follows the device the engines run on
        self.hw = hw if hw is not None else spec_for_device()
        self.budget_bytes = budget_bytes
        self.use_alpha_benchmark = use_alpha_benchmark
        self.use_module_scheduler = use_module_scheduler
        self.alpha_override = alpha_override
        self.phase_plans = phase_plans
        self.prefill_retune_factor = max(float(prefill_retune_factor), 1.0)
        self.batch: Optional[int] = None
        self.policies: Dict[str, PolicyResult] = {}
        self.engines: Dict[str, HeteGenEngine] = {}
        self._resident_store: Dict[str, jax.Array] = {}
        self._stats_tally = StreamStats()   # closed engines' busy seconds
        self._phase = "decode"
        self.step_prefetches = 0            # cross-step prefetch nudges
        self.tracer = tracer
        # trace-driven alpha recalibration (docs/OBSERVABILITY.md): when
        # set, every `recalibrate_every` decode steps the measured stream
        # speeds re-solve Eq. 10-12 and the decode plan is rebuilt if the
        # refined alpha drifted by more than `recalibrate` (absolute).
        self.recalibrate = recalibrate
        self.recalibrate_every = max(int(recalibrate_every), 1)
        self.recalibrations = 0
        self.last_fit = None                # most recent trace FitResult
        self._recal_steps = 0
        self._recal_mark = tracer.mark() if tracer else 0.0
        self.retune(batch)

    # -- phase/batch-aware planning ------------------------------------
    @property
    def policy(self) -> Optional[PolicyResult]:
        """The decode-phase plan (the historical single-plan surface)."""
        return self.policies.get("decode")

    @property
    def engine(self) -> Optional[HeteGenEngine]:
        """The decode-phase engine (the historical single-engine surface)."""
        return self.engines.get("decode")

    def retune(self, batch: int, phase: str = "decode", *,
               tokens_per_seq: Optional[int] = None) -> PolicyResult:
        """(Re)build ``phase``'s placement plan and engine for ``batch``.

        No-op when the phase already holds a plan for exactly this
        (batch, tokens_per_seq); the soft (hysteresis-guarded) prefill
        path is :meth:`_ensure_prefill_plan`.
        """
        batch = max(int(batch), 1)
        tokens_per_seq = resolve_phase_tokens(phase, tokens_per_seq)
        cur = self.policies.get(phase)
        if cur is not None and cur.batch == batch \
                and cur.tokens_per_seq == tokens_per_seq:
            return cur
        pol = build_policy(
            self.linears, self.hw, budget_bytes=self.budget_bytes,
            batch=batch, phase=phase, tokens_per_seq=tokens_per_seq,
            use_alpha_benchmark=self.use_alpha_benchmark,
            use_module_scheduler=self.use_module_scheduler)
        if self.alpha_override is not None:
            pol.plan = [
                ModulePlan(p.name, p.group, p.mode,
                           self.alpha_override if p.mode == "hetegen"
                           else p.alpha)
                for p in pol.plan]
        old = self.engines.pop(phase, None)
        if old is not None:
            # a replaced partition's busy seconds still happened: bank
            # them so finish_stats never undercounts across retunes
            self._stats_tally = self._stats_tally + old.finish_stats()
            old.close()
        self.policies[phase] = pol
        # drop store entries no current plan keeps resident BEFORE building
        # the new engine, so stale device copies are released
        keep = {p.name for r in self.policies.values()
                for p in r.plan if p.mode == "resident"}
        for name in list(self._resident_store):
            if name not in keep:
                del self._resident_store[name]
        eng = HeteGenEngine(self._host_weights, pol.plan,
                            biases=self._host_biases,
                            resident_store=self._resident_store,
                            tracer=self.tracer, trace_phase=phase,
                            wstream=self.wstream)
        eng.warm_prefetch()
        self.engines[phase] = eng
        if phase == "decode":
            self.batch = batch
        return pol

    def _ensure_prefill_plan(self, batch: int, seq: int) -> None:
        """Tune the prefill plan to the observed prompt shape, with
        multiplicative hysteresis: rebuild only when the observed
        intensity leaves [cur/f, cur*f] (prompt-length jitter across
        requests must not thrash the engine partition)."""
        cur = self.policies.get("prefill")
        intensity = max(batch, 1) * max(seq, 1)
        if cur is not None:
            f = self.prefill_retune_factor
            if cur.intensity / f <= intensity <= cur.intensity * f:
                return
        self.retune(batch, phase="prefill", tokens_per_seq=seq)

    def _ensure_verify_plan(self, batch: int, seq: int) -> None:
        """Tune the verify plan to the observed draft-run shape.

        Verification is its own phase, NOT a reuse of the prefill plan:
        admission prefills run at intensity batch x prompt_len (hundreds
        of tokens) while verify runs at batch x (k + 1) (a handful), and
        sharing one plan would make the hysteresis thrash between the two
        regimes on every interleaved step.  Same multiplicative guard so
        adaptive-k wobble does not rebuild the engine."""
        cur = self.policies.get("verify")
        intensity = max(batch, 1) * max(seq, 1)
        if cur is not None:
            f = self.prefill_retune_factor
            if cur.intensity / f <= intensity <= cur.intensity * f:
                return
        self.retune(batch, phase="verify", tokens_per_seq=seq)

    # -- tracing + trace-driven recalibration --------------------------
    def set_tracer(self, tracer: Tracer) -> None:
        """Attach a tracer to the backend and every live phase engine
        (the LLM facade calls this when ``trace=`` is enabled after the
        backend was constructed)."""
        self.tracer = tracer
        self._recal_mark = tracer.mark() if tracer else 0.0
        for phase, eng in self.engines.items():
            eng.set_tracer(tracer, trace_phase=phase)

    def recalibrate_from_trace(self, phase: str = "decode"):
        """Refine ``phase``'s alpha from the spans recorded since the
        last recalibration; returns the ``FitResult`` (or None if the
        trace has no measurable spans for that phase — e.g. an all-
        resident plan, or tracing disabled)."""
        pol = self.policies.get(phase)
        if pol is None or not self.tracer:
            return None
        spans = self.tracer.spans(since=self._recal_mark or None)
        try:
            fit = recalibrate_alpha(spans, pol.alpha, phase=phase)
        except ValueError:
            return None
        self.last_fit = fit
        return fit

    def _apply_alpha(self, phase: str, alpha: float) -> None:
        """Rebuild ``phase``'s engine with a new hetegen alpha, keeping
        the residency/streaming decisions of the existing plan."""
        pol = self.policies[phase]
        pol.plan = [ModulePlan(p.name, p.group, p.mode,
                               alpha if p.mode == "hetegen" else p.alpha)
                    for p in pol.plan]
        pol.alpha = float(alpha)
        old = self.engines.pop(phase, None)
        if old is not None:
            self._stats_tally = self._stats_tally + old.finish_stats()
            old.close()
        eng = HeteGenEngine(self._host_weights, pol.plan,
                            biases=self._host_biases,
                            resident_store=self._resident_store,
                            tracer=self.tracer, trace_phase=phase,
                            wstream=self.wstream)
        eng.warm_prefetch()
        self.engines[phase] = eng

    def _maybe_recalibrate(self) -> None:
        """Periodic trace-driven re-tune, called at the top of a decode
        or verify step — the engines are idle there, so swapping a phase
        partition is safe.  Opt-in (``recalibrate=``), with the drift
        threshold acting as hysteresis: a plan is only rebuilt when
        |refined - current| exceeds it.  Every phase that has recorded
        measurable spans since the last mark recalibrates from *its own*
        spans (phase-tagged), so a drifting verify plan re-tunes even
        though decode traffic dominates the trace."""
        if self.recalibrate is None or not self.tracer:
            return
        self._recal_steps += 1
        if self._recal_steps % self.recalibrate_every:
            return
        mark = self.tracer.mark()
        fitted = False
        for phase in ("decode", "verify"):
            if phase not in self.policies:
                continue
            fit = self.recalibrate_from_trace(phase)
            if fit is None:
                continue
            fitted = True
            cur = self.policies[phase].alpha
            if abs(fit.alpha - cur) > self.recalibrate:
                self._apply_alpha(phase, fit.alpha)
                self.recalibrations += 1
        if fitted:
            self._recal_mark = mark

    # -- LinearBackend surface -----------------------------------------
    def linear(self, x: jax.Array, name: str) -> jax.Array:
        eng = self.engines.get(self._phase) or self.engines["decode"]
        return eng.linear(x, name)

    def init_cache(self, batch: int, max_len: int) -> Dict:
        return M.init_backend_cache(self.cfg, batch, max_len)

    def init_paged_cache(self, batch: int, max_len: int, *,
                         page_size: int = 16,
                         n_pages: Optional[int] = None,
                         kv_dtype: Optional[str] = None,
                         check: bool = False) -> PagedKVCache:
        return PagedKVCache(self.cfg, batch, max_len, page_size=page_size,
                            n_pages=n_pages, kv_dtype=kv_dtype, check=check)

    def prefill(self, batch: Dict, cache: Dict) -> Tuple[Dict, jax.Array]:
        if self.phase_plans:
            if "tokens" in batch:
                b, s = batch["tokens"].shape
            else:
                b, s = batch["embeds"].shape[:2]
            self._ensure_prefill_plan(b, s)
            self._phase = "prefill"
        try:
            return M.backend_prefill(self.cfg, self.shared, batch, cache,
                                     linear=self.linear, ops=self._ops)
        finally:
            self._phase = "decode"

    def decode(self, token: jax.Array, cache: Dict
               ) -> Tuple[Dict, jax.Array]:
        self._maybe_recalibrate()
        return M.backend_decode(self.cfg, self.shared, token, cache,
                                linear=self.linear, ops=self._ops)

    def verify(self, batch: Dict, cache: Dict) -> Tuple[Dict, jax.Array]:
        """Speculative scoring pass under the "verify" phase plan —
        intensity batch x (k + 1), the prefill-like regime where alpha
        pushes toward the accelerator even though the step advances the
        decode frontier."""
        self._maybe_recalibrate()
        if self.phase_plans:
            b, s = batch["tokens"].shape
            self._ensure_verify_plan(b, s)
            self._phase = "verify"
        try:
            return M.backend_prefill(self.cfg, self.shared, batch, cache,
                                     linear=self.linear, ops=self._ops,
                                     all_logits=True)
        finally:
            self._phase = "decode"

    def prefetch_next_step(self) -> None:
        """Drive step N+1's pins while step N's host tail drains.

        The engine's wrap-around prefetch order already points the last
        module of a decode step at the first module of the next one
        (:func:`repro.core.param_manager.plan_prefetch_order`), but that
        wrap prefetch is issued while the last module's own slot is still
        staged — when the ring is full it silently loses.  The scheduler
        calls this between a decode step's math and its host-side
        sampling/bookkeeping: by then every slot has been released, so
        re-issuing the first-of-each-group prefetch is guaranteed to
        land, and the pin thread stages the next step concurrently with
        sampling (ROADMAP decode-overlap item).  Idempotent and
        non-blocking — modules already staged are left alone.
        """
        eng = self.engines.get("decode")
        if eng is not None:
            eng.warm_prefetch()
            self.step_prefetches += 1

    # -- stats over all phase engines ----------------------------------
    def reset_stats(self) -> None:
        self._stats_tally = StreamStats()
        for eng in self.engines.values():
            eng.reset_stats()

    def finish_stats(self) -> StreamStats:
        out = self._stats_tally
        for eng in self.engines.values():
            out = out + eng.finish_stats()
        return out

    def device_resident_bytes(self) -> int:
        seen: Dict[str, int] = {}
        for eng in self.engines.values():
            for name, arr in eng._resident.items():
                seen[name] = int(np.prod(arr.shape)) * arr.dtype.itemsize
        return sum(seen.values())

    def pinned_overhead_bytes(self) -> int:
        return sum(eng.pinned_overhead_bytes()
                   for eng in self.engines.values())

    def close(self) -> None:
        for eng in self.engines.values():
            eng.close()
        self.engines.clear()
        self._resident_store.clear()
