"""Continuous batching: a pure executor under a pluggable scheduler.

Requests join/leave a fixed pool of ``max_slots`` decode slots without
stopping the batch.  *Who* occupies those slots is no longer this
module's business: every admit/preempt/resume decision lives in
:class:`repro.serving.scheduler.Scheduler` behind the
:class:`repro.serving.scheduler.SchedulerPolicy` seam (``fcfs`` /
``priority`` / ``fair_share``), and the batcher merely applies the
scheduler's per-step :class:`repro.serving.scheduler.StepPlan`:

  * **preempt** — save the victim's KV pages to host memory (swap mode)
    and clear its slot;
  * **start** — restore saved pages (swap resume) or prefill
    ``prompt + generated`` through a batch-1 view (fresh admissions and
    recompute resumes are literally the same code path — a fresh request
    just has no ``generated`` yet);
  * **decode** — advance every active slot one token (inactive slots in
    dense mode decode garbage that is masked out — the standard
    static-shape TPU pattern; paged mode *compacts* to the active
    block-table rows instead).

Per-slot sequence lengths are first-class: the model's decode path accepts
a vector ``len`` and scatters each slot's new K/V at its own position.

The batcher schedules over any :mod:`repro.serving.backends` driver: the
default is the jitted scan-stacked resident path, but
``backend=HeteGenBackend(...)`` runs the SAME executor over
HeteGen-offloaded weights.  Between a decode step's math and its host-side
sampling/bookkeeping the executor nudges the offload engine's pinned ring
(``backend.prefetch_next_step()``): the ring's wrap-around prefetch order
already points the last module of step N at the first module of step N+1,
so the nudge retries any wrap prefetch that found the ring full — step
N+1's pins run while step N's host work drains (ROADMAP perf item).

Sampling is **per request** (docs/SERVING.md): each submit may carry its
own :class:`repro.serving.sampling.SamplingParams`, rows of one decode
batch are sampled under their own parameters (row-vectorized sampler),
and every request owns a PRNG stream keyed by its id and generated-token
count — never by batch-row number.  Scheduling (compaction, preemption,
resume) therefore cannot perturb tokens: paged and dense, pressured and
unpressured runs are token-identical.  ``SamplingParams.logprobs``
additionally records each sampled token's log-probability (and top-k
alternatives) straight out of the sampler's existing sort.

``paged=True`` swaps the dense per-layer cache for the
:class:`repro.serving.kv_cache.PagedKVCache` subsystem; with
``optimistic=True`` (the default) admission maps only the prompt's pages
and the scheduler grows each running slot one decode position per step,
so page pressure triggers policy-driven preemption instead of
head-of-queue blocking (``optimistic=False`` restores the classic
``prompt + max_new`` reservation).  ``kv_dtype="int8"`` stores q8 pages.

``retune_hysteresis`` (with a retune-capable backend, i.e. HeteGen)
re-tunes the decode placement plan when the *executed* decode batch
drifts from the planned batch by more than the hysteresis margin —
§4.1's cost model shifts alpha with compute intensity, but rebuilding
the engine every time one request finishes would thrash; the margin
makes retunes sticky.  Only paged mode executes occupancy-sized batches
(compaction), so only paged mode ever re-tunes.

The batcher owns backend lifetime when it constructed the backend (or
when handed one with ``own_backend=True``): ``close()`` — or leaving the
``with`` block — shuts down the owned backend's engine threads.
"""

from __future__ import annotations

import contextlib
import itertools
import time
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.serving.backends import ScanResidentBackend
from repro.serving.kv_cache import pool_keys, pool_view, slot_view
from repro.serving.sampling import (SamplerConfig, SamplingParams, greedy,
                                    pack_sampling, request_key, sample_rows,
                                    step_key)
from repro.serving.scheduler import (PREFILLING, RequestState, RUNNING,
                                     Scheduler, SchedulerPolicy)
from repro.serving.speculative import (AdaptiveK, SpecConfig, SpecStats,
                                       accept_row, logprob_record)
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.tracer import NULL_TRACER, Tracer

# back-compat: PR 3 exposed the queue entry as batcher.Request
Request = RequestState


class ContinuousBatcher:
    def __init__(self, cfg: ModelConfig, params: Optional[Dict] = None, *,
                 max_slots: int = 4, max_len: int = 512,
                 backend=None, sampler: SamplerConfig = SamplerConfig(),
                 seed: int = 0, paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 retune_hysteresis: Optional[int] = None,
                 own_backend: Optional[bool] = None,
                 policy: Union[str, SchedulerPolicy, None] = "fcfs",
                 optimistic: bool = True,
                 preempt_mode: Optional[str] = None,
                 chunk_tokens: Optional[int] = None,
                 prefix_dedupe: Optional[bool] = None,
                 spec: Optional[SpecConfig] = None,
                 selfcheck: bool = False,
                 tracer: Tracer = NULL_TRACER,
                 metrics: Optional[MetricsRegistry] = None):
        pattern = bool(cfg.block_pattern)
        if cfg.family in ("ssm", "hybrid", "encdec") and not pattern:
            raise NotImplementedError(
                "continuous batching supports transformer KV caches and "
                "block-pattern hybrids")
        if pattern:
            # a block-pattern hybrid's Mamba layers carry per-slot
            # recurrent state beside the attention pages: it lives in the
            # paged cache, is rebuilt by recompute after a preemption (a
            # swap saves pages only), and cannot be aliased by prefix
            # dedupe or rolled back by speculative decoding
            if not paged:
                raise NotImplementedError(
                    f"{cfg.name}: block-pattern hybrids need paged=True")
            if preempt_mode == "swap":
                raise NotImplementedError(
                    f"{cfg.name}: swap-mode preemption saves KV pages, not "
                    "the Mamba state; use preempt_mode='recompute'")
            if prefix_dedupe:
                raise NotImplementedError(
                    f"{cfg.name}: prefix dedupe would share pages without "
                    "the Mamba state of the shared prefix")
            if spec is not None:
                raise NotImplementedError(
                    f"{cfg.name}: speculative decoding cannot roll back "
                    "the Mamba state of rejected drafts")
            preempt_mode, prefix_dedupe = "recompute", False
        if backend is None and params is None:
            raise ValueError("ContinuousBatcher needs params or a backend")
        self.cfg = cfg
        # own the backend when we constructed it; callers handing one over
        # transfer ownership with own_backend=True
        self._own_backend = backend is None if own_backend is None \
            else bool(own_backend)
        # observability (docs/OBSERVABILITY.md): spans land on the "step"
        # and "phase" tracks here, the backend's engines add the stream
        # tracks; the registry holds live serving counters and absorbs
        # the legacy stats() dicts on snapshot
        self.tracer = tracer
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._step_no = 0
        self.backend = backend or ScanResidentBackend(cfg, params)
        if tracer and hasattr(self.backend, "set_tracer"):
            self.backend.set_tracer(tracer)
        if hasattr(self.backend, "retune"):
            # the decode batch is the slot count — enforce the documented
            # contract instead of trusting the caller's constructed plan
            self.backend.retune(max_slots)
        self.max_slots = max_slots
        self.max_len = max_len
        self.default_sampling = SamplingParams.from_config(sampler)
        # lint: allow[prng-discipline] the one base key request_key folds
        # request ids into; every sampling draw derives from it per request
        self._base_key = jax.random.PRNGKey(seed)
        self.paged = paged
        self.kv = None
        if paged:
            self.kv = self.backend.init_paged_cache(
                max_slots, max_len, page_size=page_size, n_pages=n_pages,
                kv_dtype=kv_dtype, check=selfcheck)
            self.cache = self.kv.init_cache()
        else:
            self.cache = self.backend.init_cache(max_slots, max_len)
        # the decision seam: admission order, preemption victims, page
        # growth — everything except device work (docs/SERVING.md)
        self.scheduler = Scheduler(policy, max_slots, max_len, kv=self.kv,
                                   optimistic=optimistic,
                                   preempt_mode=preempt_mode,
                                   chunk_tokens=chunk_tokens,
                                   prefix_dedupe=prefix_dedupe,
                                   tracer=tracer)
        # per-slot lengths (vector 'len' drives per-slot scatter updates)
        self.cache["len"] = jnp.zeros((max_slots,), jnp.int32)
        # dense chunked prefill accumulates each slot's KV in a private
        # batch-1 cache (merged into the global cache only on the final
        # chunk, so full-width decode's masked garbage writes can never
        # land inside a half-prefilled slot row)
        self._pending_dense: Dict[int, Dict] = {}
        self.tokens = jnp.zeros((max_slots,), jnp.int32)
        self._ids = itertools.count()
        self.retune_hysteresis = retune_hysteresis
        self._plan_batch = max_slots
        self.retunes = 0
        # speculative decoding: CPU-side drafting + batched verification
        # (docs/SERVING.md).  The batcher owns the drafter's lifetime.
        self.spec = spec
        self.spec_stats = SpecStats()
        self.spec_by_req: Dict[int, SpecStats] = {}
        self._adaptive: Optional[AdaptiveK] = None
        if spec is not None:
            if not hasattr(self.backend, "verify"):
                raise ValueError(
                    "speculative decoding needs a backend exposing "
                    "verify(batch, cache); "
                    f"{type(self.backend).__name__} does not")
            if spec.adaptive:
                self._adaptive = AdaptiveK(spec.k, spec.k_min, spec.k_max)
        self._closed = False
        # packed sampling params change only when slot->request assignment
        # does (admit/release), not every step — cache the device arrays
        self._pack_sig: Optional[tuple] = None
        self._packed = None
        self._packed_lp: Optional[int] = None

    # -- scheduler views the facade and tests read ----------------------
    @property
    def requests(self) -> Dict[int, RequestState]:
        return self.scheduler.requests

    @property
    def queue(self) -> List[RequestState]:
        """Everything still wanting a slot (waiting + preempted)."""
        return self.scheduler.pending

    @property
    def active(self) -> np.ndarray:
        return self.scheduler.active_mask()

    @property
    def policy(self) -> SchedulerPolicy:
        return self.scheduler.policy

    # ------------------------------------------------------------------
    def submit(self, prompt: List[int], max_new: int,
               eos: Optional[int] = None, *,
               sampling: Optional[SamplingParams] = None,
               rid: Optional[int] = None,
               priority: int = 0) -> int:
        """Queue a request.  ``sampling`` defaults to the batcher-wide
        config; ``rid`` lets an owning facade keep one id space;
        ``priority`` matters to priority-aware scheduler policies."""
        rid = next(self._ids) if rid is None else rid
        sp = self.default_sampling if sampling is None else sampling
        st = RequestState(rid, list(prompt), max_new, eos, sampling=sp,
                          key=request_key(self._base_key, rid, sp),
                          priority=priority)
        self.scheduler.submit(st)
        return rid

    def _sample_slot_rows(self, logits: jax.Array,
                          slots: List[int]) -> jax.Array:
        """Sample one token per logits row, row i belonging to slot
        ``slots[i]``.  Each occupied slot draws under its request's own
        params with the key for its next token; vacant rows (the dense
        path's masked garbage) sample greedily with a dead key, so they
        consume no entropy and cannot perturb real requests.  Rows whose
        request asked for logprobs get their per-token record appended
        here, straight out of the sampler's existing sort."""
        with self.tracer.span("sample", track="sample", rows=len(slots)):
            return self._sample_slot_rows_traced(logits, slots)

    def _sample_slot_rows_traced(self, logits: jax.Array,
                                 slots: List[int]) -> jax.Array:
        slot_req = self.scheduler.slot_req
        params, keys = [], []
        for s in slots:
            req = slot_req[s]
            # a mid-prefill slot's decode row is masked garbage exactly
            # like a vacant one — its real first token is sampled by the
            # final chunk, after the status flips to running
            if req is None or req.status == PREFILLING:
                params.append(SamplingParams())
                keys.append(jnp.zeros((2,), jnp.uint32))
            else:
                params.append(req.sampling)
                keys.append(step_key(req.key, len(req.generated)))
        lp_k = [p.logprobs for p in params if p.logprobs is not None]
        if not lp_k and all(p.kind == "greedy" for p in params):
            # the default serving config: skip the full-vocab sort the
            # mixed-kind sampler needs (greedy rows never draw entropy,
            # so this is exactly equivalent)
            return greedy(logits)
        sig = tuple((s, -1 if slot_req[s] is None
                     or slot_req[s].status == PREFILLING
                     else slot_req[s].rid)
                    for s in slots)
        if sig != self._pack_sig:
            self._pack_sig = sig
            self._packed = pack_sampling(params)
            self._packed_lp = max(lp_k) if lp_k else None
        if self._packed_lp is None:
            return sample_rows(logits, jnp.stack(keys), self._packed)
        toks, lp = sample_rows(logits, jnp.stack(keys), self._packed,
                               top_logprobs=self._packed_lp)
        chosen = np.asarray(lp["logprob"])
        top_ids = np.asarray(lp["top_tokens"])
        top_lp = np.asarray(lp["top_logprobs"])
        for i, s in enumerate(slots):
            req = slot_req[s]
            if req is None or req.sampling.logprobs is None:
                continue
            k = req.sampling.logprobs
            req.logprobs.append({
                "token": int(toks[i]),
                "logprob": float(chosen[i]),
                "top": {int(t): float(l)
                        for t, l in zip(top_ids[i, :k], top_lp[i, :k])},
            })
        return toks

    # -- plan application ----------------------------------------------
    def _apply_preempt(self, st: RequestState) -> None:
        """Device side of an eviction: gather the victim's KV pages to
        host (swap mode — before anything can rewrite them) and clear its
        slot length.  Recompute mode keeps only the token ids."""
        if st.swap_block_ids is not None:
            ids = jnp.asarray(st.swap_block_ids, jnp.int32)
            # lint: allow[hot-path-sync] swap-mode preemption host-saves
            # the victim's KV pages by design; it runs on the rare
            # PagesExhausted path, not on a normal decode step
            st.saved_kv = {k: np.asarray(v[ids])
                           for k, v in self.cache.items()
                           if k.startswith("pages_")}
        self._pending_dense.pop(st.slot, None)
        self.cache["len"] = self.cache["len"].at[st.slot].set(0)
        st.slot = None

    def _start(self, st: RequestState) -> None:
        """Device side of an admission: swap-restore saved pages, or
        prefill ``prompt + generated`` (fresh and recompute resumes)."""
        slot = st.slot
        if st.saved_kv is not None:
            # token-exact resume: scatter the saved KV bits into the
            # freshly mapped pages; the pending input token is the last
            # one generated before eviction
            ids = jnp.asarray(
                self.kv.mapped_pages(slot)[:len(st.swap_block_ids)],
                jnp.int32)
            for key, saved in st.saved_kv.items():
                self.cache[key] = self.cache[key].at[ids].set(
                    jnp.asarray(saved))
            self.cache["len"] = self.cache["len"].at[slot].set(st.saved_len)
            self.tokens = self.tokens.at[slot].set(st.generated[-1])
            st.saved_kv = None
            st.swap_block_ids = None
            return
        toks = jnp.asarray([st.prompt + st.generated], jnp.int32)
        if self.paged:
            self._reset_state(slot)
            logits = self._prefill_paged_slot(slot, toks)
        else:
            logits = self._prefill_dense_slot(slot, toks)
        first = self._sample_slot_rows(logits, [slot])
        self.cache["len"] = self.cache["len"].at[slot].set(
            toks.shape[1])
        self.tokens = self.tokens.at[slot].set(first[0])
        with self._readback("prefill", 1):
            st.generated.append(int(first[0]))
        self._maybe_finish(st)

    def _prefill_dense_slot(self, slot: int, toks: jax.Array) -> jax.Array:
        """Batch-1 prefill into a fresh dense cache, then whole-slice
        merge of every leaf into the global cache (the copy the paged
        path exists to avoid)."""
        axis = self.backend.cache_batch_axis
        one_cache = self.backend.init_cache(1, self.max_len)
        one_cache, logits = self.backend.prefill({"tokens": toks},
                                                 one_cache)

        # merge slot: every cache leaf carries batch at `axis`
        def merge(glob, one):
            if glob.ndim == 0 or glob.shape == ():
                return glob
            return jax.lax.dynamic_update_slice_in_dim(
                glob, one.astype(glob.dtype), slot, axis=axis)
        for key in self.cache:
            if key == "len":
                continue
            self.cache[key] = merge(self.cache[key], one_cache[key])
        return logits

    def _prefill_paged_slot(self, slot: int, toks: jax.Array) -> jax.Array:
        """Prefill through a batch-1 block-table view: the page pools are
        shared, so the prompt's KV scatters straight into the pages just
        mapped for this slot — admission moves exactly the new tokens,
        never a (1, max_len) cache slice."""
        self.cache["block_tables"] = self.kv.device_block_tables()
        self.scheduler.tables_dirty = False
        one = slot_view(self.cache, slot)
        one, logits = self.backend.prefill({"tokens": toks}, one)
        for key in pool_keys(one):
            self.cache[key] = one[key]
        return logits

    def _reset_state(self, slot: int) -> None:
        """Zero the slot's recurrent state rows as it admits a sequence
        (a no-op for caches that hold pages only)."""
        if "slot_rows" in self.cache:
            self.cache = self.kv.reset_state(self.cache, slot)
            self.metrics.counter("serve.state_resets").inc()

    def _start_batch(self, sts: List[RequestState]) -> None:
        """Admit several same-length fresh requests in ONE prefill call
        instead of a batch-1 Python loop.  Attention rows are independent,
        so the batched call is token-identical to per-slot admission —
        it just amortizes the weight streaming (the whole point on an
        offload backend, where prefill cost is dominated by moving
        weights over the PCIe link once per call)."""
        slots = [st.slot for st in sts]
        toks = jnp.asarray([st.prompt + st.generated for st in sts],
                           jnp.int32)
        n = toks.shape[1]
        if self.paged:
            self.cache["block_tables"] = self.kv.device_block_tables()
            self.scheduler.tables_dirty = False
            for slot in slots:
                self._reset_state(slot)
            view = pool_view(self.cache, jnp.asarray(slots))
            view["len"] = jnp.zeros((), jnp.int32)
            view, logits = self.backend.prefill({"tokens": toks}, view)
            for key in pool_keys(view):
                self.cache[key] = view[key]
        else:
            axis = self.backend.cache_batch_axis
            grp = self.backend.init_cache(len(sts), self.max_len)
            grp, logits = self.backend.prefill({"tokens": toks}, grp)
            for key in self.cache:
                if key == "len":
                    continue
                glob = self.cache[key]
                if glob.ndim == 0 or glob.shape == ():
                    continue
                for i, slot in enumerate(slots):
                    row = jax.lax.dynamic_slice_in_dim(grp[key], i, 1,
                                                       axis=axis)
                    glob = jax.lax.dynamic_update_slice_in_dim(
                        glob, row.astype(glob.dtype), slot, axis=axis)
                self.cache[key] = glob
        firsts = self._sample_slot_rows(logits, slots)
        with self._readback("prefill", len(sts)):
            for i, st in enumerate(sts):
                self.cache["len"] = self.cache["len"].at[st.slot].set(n)
                self.tokens = self.tokens.at[st.slot].set(firsts[i])
                st.generated.append(int(firsts[i]))
                self._maybe_finish(st)

    def _prefill_chunk(self, st: RequestState) -> None:
        """Advance one chunk of a chunked prefill: run tokens
        ``[prefill_cursor, prefill_target)`` through ``backend.prefill``
        at the right KV offset.  Intermediate chunks only write KV; the
        final chunk samples the request's first token and flips it to
        running, so the slot joins this same step's decode — exactly
        :meth:`_start`'s semantics, just spread over several steps."""
        slot = st.slot
        start, end = st.prefill_cursor, st.prefill_target
        seq = st.prompt + st.generated
        n = len(seq)
        toks = jnp.asarray([seq[start:end]], jnp.int32)
        if self.paged:
            self.cache["block_tables"] = self.kv.device_block_tables()
            self.scheduler.tables_dirty = False
            if start == 0:
                self._reset_state(slot)
            one = slot_view(self.cache, slot, length=start)
            one, logits = self.backend.prefill({"tokens": toks}, one)
            for key in pool_keys(one):
                self.cache[key] = one[key]
        else:
            one_cache = self._pending_dense.get(slot)
            if one_cache is None:
                one_cache = self.backend.init_cache(1, self.max_len)
            one_cache, logits = self.backend.prefill({"tokens": toks},
                                                     one_cache)
            self._pending_dense[slot] = one_cache
        st.prefill_cursor = end
        if end < n:
            return
        # final chunk — merge the private dense cache into the slot row
        # (paged chunks scattered straight into the slot's pages)
        if not self.paged:
            one_cache = self._pending_dense.pop(slot)
            axis = self.backend.cache_batch_axis
            for key in self.cache:
                if key == "len":
                    continue
                glob = self.cache[key]
                if glob.ndim == 0 or glob.shape == ():
                    continue
                self.cache[key] = jax.lax.dynamic_update_slice_in_dim(
                    glob, one_cache[key].astype(glob.dtype), slot,
                    axis=axis)
        st.status = RUNNING            # before sampling: the row is real
        first = self._sample_slot_rows(logits, [slot])
        self.cache["len"] = self.cache["len"].at[slot].set(n)
        self.tokens = self.tokens.at[slot].set(first[0])
        with self._readback("prefill", 1):
            st.generated.append(int(first[0]))
        self._maybe_finish(st)

    def _readback(self, phase: str, rows: int):
        """A ``token_readback`` span on the ``wait`` track around reading
        ``rows`` sampled tokens back to the host, one sync each."""
        return self.tracer.span("token_readback", track="wait",
                                module="batcher", phase=phase, syncs=rows)

    def _maybe_finish(self, st: RequestState) -> None:
        hit_eos = (st.eos is not None and st.generated
                   and st.generated[-1] == st.eos)
        if hit_eos or len(st.generated) >= st.max_new:
            st.finish_reason = "eos" if hit_eos else "length"
            slot = st.slot
            self.scheduler.finish(st)
            if slot is not None:
                self.cache["len"] = self.cache["len"].at[slot].set(0)
                st.slot = None
            if self.spec is not None:
                self.spec.drafter.release(st.rid)
                if self._adaptive is not None:
                    self._adaptive.release(st.rid)

    # ------------------------------------------------------------------
    def step(self) -> int:
        """Run one scheduler step: apply the policy's plan (preempt /
        admit / resume / grow pages), then advance all active slots one
        token.  Returns the number of active slots after the step.

        With speculative decoding configured, drafting happens host-side
        BEFORE the plan (the scheduler needs each request's ``k_eff + 1``
        advance to reserve the whole draft run's pages up front), and the
        decode step becomes a verify step that can advance a slot several
        tokens; proposals for slots the plan preempts are simply dropped
        (no entropy was consumed, and deterministic drafters re-propose
        identically on resume — mid-speculation preemption stays
        token-identical).

        Each step records one ``step`` span (its ``phase`` attr names
        the dominant work) plus per-phase spans on the ``phase`` track,
        and feeds the live serving metrics — all no-ops with the null
        tracer/default registry idle.
        """
        self._step_no += 1
        t0 = time.perf_counter()
        toks_before = sum(len(r.generated) for r in self.requests.values())
        sp = self.tracer.span(f"step{self._step_no}", track="step")
        with sp:
            n = self._step_inner(sp)
        m = self.metrics
        m.counter("serve.steps").inc()
        m.counter("serve.tokens").inc(
            sum(len(r.generated) for r in self.requests.values())
            - toks_before)
        m.histogram("serve.step_s").observe(time.perf_counter() - t0)
        m.gauge("serve.active_slots").set(n)
        return n

    def _step_inner(self, sp) -> int:
        if self.kv is not None and self.kv.check:
            # selfcheck mode: prove the allocator invariants at the step
            # boundary too, so drift introduced between the per-op hooks
            # (e.g. direct metadata edits) surfaces before the next plan
            self.kv.validate()
        with self.tracer.span("plan", track="phase"):
            proposals = self._draft_proposals() if self.spec is not None \
                else None
            advances = None
            if proposals:
                advances = {rid: len(d) + 1 for rid, d in proposals.items()}
            plan = self.scheduler.plan(advances)
        admit_cm = self.tracer.span("prefill", track="phase") \
            if (plan.preempt or plan.start or plan.prefill) \
            else contextlib.nullcontext()
        with admit_cm:
            for st in plan.preempt:
                self._apply_preempt(st)
            # group same-length fresh admissions into one prefill call;
            # swap restores and odd lengths keep the batch-1 path
            fresh: Dict[int, List[RequestState]] = {}
            for st in plan.start:
                if st.saved_kv is not None:
                    self._start(st)
                else:
                    fresh.setdefault(
                        len(st.prompt) + len(st.generated), []).append(st)
            for sts in fresh.values():
                if len(sts) == 1:
                    self._start(sts[0])
                else:
                    self._start_batch(sts)
            for st in plan.prefill:
                self._prefill_chunk(st)
        if self.paged and self.scheduler.tables_dirty:
            # page growth / release since the last export (admission
            # prefills re-export on their own)
            self.cache["block_tables"] = self.kv.device_block_tables()
            self.scheduler.tables_dirty = False
        active = self.scheduler.active_mask()
        if not active.any():
            sp.set(phase="prefill" if (plan.start or plan.prefill)
                   else "idle")
            return 0
        occ = int(active.sum())
        # the batch a decode step actually executes: paged decode compacts
        # to the active slots (cheap — a block-table row gather), dense
        # decode always runs the full slot width (inactive slots compute
        # masked garbage, the static-shape pattern)
        executed = occ if self.paged else self.max_slots
        if (self.retune_hysteresis is not None
                and hasattr(self.backend, "retune")
                and abs(executed - self._plan_batch)
                > self.retune_hysteresis):
            # executed batch drifted past the hysteresis margin: rebuild
            # the decode placement plan for it (ROADMAP item); small
            # oscillations stay on the current plan.  §4.1's cost model
            # only sees the executed width, so dense mode never re-tunes
            # on occupancy.  The prefill plan is the backend's own
            # business (phase-tuned on observed prompt shapes).
            self.backend.retune(executed, phase="decode")
            self._plan_batch = executed
            self.retunes += 1
        if proposals:
            # drop proposals whose request the plan preempted or that
            # lost their slot — then run draft + undrafted rows through
            # one verify step (an undrafted row's bonus draw IS the
            # baseline decode draw, so mixing costs nothing)
            proposals = {rid: d for rid, d in proposals.items()
                         if d and rid in self.requests
                         and self.requests[rid].status == RUNNING}
        if proposals:
            sp.set(phase="verify")
            with self.tracer.span("verify", track="phase"):
                self._spec_step(proposals, active)
            return int(self.scheduler.active_mask().sum())
        sp.set(phase="decode")
        running = self.scheduler.running()
        with self.tracer.span("decode", track="phase", rows=executed,
                              kv_tokens=sum(st.kv_len + 1
                                            for st in running)):
            if self.paged and occ < self.max_slots:
                self._decode_active_slots(active)
            else:
                self.cache, logits = self.backend.decode(self.tokens,
                                                         self.cache)
                self._prefetch_next_step()
                self.tokens = self._sample_slot_rows(
                    logits, list(range(self.max_slots)))
        nxt = self.tokens
        with self._readback("decode", len(running)):
            for st in running:
                st.generated.append(int(nxt[st.slot]))
                self._maybe_finish(st)
        return int(self.scheduler.active_mask().sum())

    def _draft_proposals(self) -> Dict[int, List[int]]:
        """Host-side drafting over the running slots, capped per request
        so a fully-accepted run can never overshoot ``max_new`` (the
        bonus token needs headroom of 1) or ``max_len`` (the run's KV
        must fit: ``kv_len + k + 1 <= max_len``)."""
        out: Dict[int, List[int]] = {}
        for st in self.scheduler.running():
            k = self._adaptive.k_for(st.rid) if self._adaptive is not None \
                else self.spec.k
            k = min(k, st.max_new - len(st.generated) - 1,
                    self.max_len - st.kv_len - 1)
            if k <= 0:
                continue
            d = self.spec.drafter.propose(st.rid, st.prompt + st.generated,
                                          k)
            if d:
                out[st.rid] = [int(t) for t in d[:k]]
        return out

    def _spec_step(self, proposals: Dict[int, List[int]],
                   active: np.ndarray) -> None:
        """Draft -> verify -> accept -> rollback, as one step.

        Every running slot joins the verify batch — drafted rows carry
        ``[pending] + drafts``, undrafted rows just their pending token —
        padded to the widest run.  One ``backend.verify`` call scores all
        rows at their own ``kv_len`` (the paged-prefill kernel's
        per-batch ``kv_offset``); acceptance runs host-side per row under
        the request's own sampling params and PRNG stream; rejected
        drafts roll back as metadata (``PagedKVCache.truncate`` /
        a dense length reset — stale KV past the new length is masked
        and overwritten before it could ever be attended, the same
        argument that makes chunked prefill exact)."""
        slot_req = self.scheduler.slot_req
        slots = [int(s) for s in np.flatnonzero(active)]
        drafts = {s: proposals.get(slot_req[s].rid, []) for s in slots}
        width = max(len(d) for d in drafts.values()) + 1

        def row_tokens(s: int) -> List[int]:
            st = slot_req[s]
            d = drafts[s]
            return [st.generated[-1]] + d + [0] * (width - 1 - len(d))

        if self.paged:
            idx = jnp.asarray(slots)
            toks = jnp.asarray([row_tokens(s) for s in slots], jnp.int32)
            sub = {k: v for k, v in self.cache.items()
                   if k.startswith("pages_")}
            sub["block_tables"] = self.cache["block_tables"][idx]
            sub["len"] = self.cache["len"][idx]
            sub, logits = self.backend.verify({"tokens": toks}, sub)
            self._prefetch_next_step()
            for key in sub:
                if key.startswith("pages_"):
                    self.cache[key] = sub[key]
            row_of = {s: i for i, s in enumerate(slots)}
        else:
            # dense runs full width (static shapes); garbage rows of
            # vacant/prefilling slots are masked and their cache rows are
            # wholly overwritten at admission, exactly like plain decode.
            # Keep their lengths: verify bumps every row's len by the
            # padded width, but the real new lengths are only known after
            # acceptance — restore, then set per-slot below.
            # lint: allow[hot-path-sync] host mirror of slot lengths for
            # the accept/reject loop; dense "len" is a small host-side row
            lens_before = np.asarray(self.cache["len"])
            toks = jnp.asarray(
                [row_tokens(s) if active[s] else [0] * width
                 for s in range(self.max_slots)], jnp.int32)
            self.cache, logits = self.backend.verify({"tokens": toks},
                                                     self.cache)
            self._prefetch_next_step()
            self.cache["len"] = jnp.asarray(lens_before)
            row_of = {s: s for s in slots}

        with self.tracer.span("sample", track="sample", rows=len(slots)):
            # lint: allow[hot-path-sync] speculative accept/reject is
            # host-side by design (point-mass rejection sampling over the
            # verify logits); the step's one sync, same budget as sampling
            lg = np.asarray(logits, np.float32)     # (rows, width, V)
        for s in slots:
            st = slot_req[s]
            m = len(drafts[s])
            rows = lg[row_of[s], :m + 1]
            emitted = accept_row(rows, drafts[s], st.sampling, st.key,
                                 len(st.generated))
            n_full = len(emitted) - 1            # drafts accepted, pre-cut
            if st.eos is not None and st.eos in emitted:
                emitted = emitted[:emitted.index(st.eos) + 1]
            accepted = min(len(emitted), n_full)
            if m > 0:
                self.spec_stats.record(m, accepted)
                self.spec_by_req.setdefault(st.rid, SpecStats()) \
                    .record(m, accepted)
                if self._adaptive is not None:
                    self._adaptive.update(st.rid, m, accepted)
            if st.sampling.logprobs is not None:
                for j, t in enumerate(emitted):
                    st.logprobs.append(
                        logprob_record(rows[j], t, st.sampling.logprobs))
            st.generated.extend(emitted)
            # rollback: kv_len now counts only pending + accepted drafts;
            # pages past it unmap (paged) and the length vector shrinks
            new_len = st.kv_len
            if self.paged:
                self.cache = self.kv.truncate(self.cache, s, new_len)
                self.scheduler.tables_dirty = True
            self.cache["len"] = self.cache["len"].at[s].set(new_len)
            self.tokens = self.tokens.at[s].set(emitted[-1])
            self._maybe_finish(st)

    def _prefetch_next_step(self) -> None:
        """Kick step N+1's pins while step N's host tail (sampling,
        bookkeeping) drains.  The engine's wrap-around prefetch order
        already points each step's last module at the next step's first,
        but that wrap prefetch silently loses when the pinned ring is
        still full — retrying here, after the step's linears released
        their slots, lets the pin thread stage the next step's first
        module of every group concurrently with everything below."""
        if hasattr(self.backend, "prefetch_next_step"):
            self.backend.prefetch_next_step()

    def _decode_active_slots(self, active: np.ndarray) -> None:
        """One decode step over the active slots only.

        The paged cache makes batch compaction a metadata operation: the
        pools are global, so selecting the active block-table / length /
        token rows yields a smaller decode batch whose GEMMs match the
        real occupancy (what ``retune`` plans for) — inactive slots cost
        nothing and write nothing.  Results scatter back by slot index.
        """
        slots = np.flatnonzero(active)
        idx = jnp.asarray(slots)
        sub = pool_view(self.cache, idx)
        sub["len"] = self.cache["len"][idx]
        sub, logits = self.backend.decode(self.tokens[idx], sub)
        self._prefetch_next_step()
        for key in pool_keys(sub):
            self.cache[key] = sub[key]
        self.cache["len"] = self.cache["len"].at[idx].set(sub["len"])
        nxt = self._sample_slot_rows(logits, list(slots))
        self.tokens = self.tokens.at[idx].set(nxt)

    def run_until_done(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        for _ in range(max_steps):
            # resident() (not active) — a slot mid-chunked-prefill is not
            # decoding yet but still owes work
            if not self.queue and not self.scheduler.resident():
                break
            self.step()
        return {rid: r.generated for rid, r in self.requests.items()}

    # ------------------------------------------------------------------
    def close(self) -> None:
        """Release the backend when this batcher owns it (an offload
        backend holds engine threads and pinned rings — leaking it leaks
        non-daemon threads).  Idempotent; safe on shared backends (no-op
        unless owning)."""
        if self._closed:
            return
        self._closed = True
        if self.kv is not None:
            # end-of-life audit: raises PagedCacheCorruption on leaked
            # pages when the cache was built with check=True
            self.kv.close()
        if self._own_backend:
            self.backend.close()

    def __enter__(self) -> "ContinuousBatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
