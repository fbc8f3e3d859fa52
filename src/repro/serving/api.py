"""The request-level serving front door (docs/SERVING.md).

One facade, :class:`LLM`, covers every serving shape this repo has:
resident jitted generation, HeteGen-offloaded generation, continuous
batching (dense or paged KV), and streaming — behind a request-level API:

    with LLM(cfg, params) as llm:                       # resident
        outs = llm.generate([p1, p2], max_new=32)       # blocking batch

    llm = LLM(cfg, backend=HeteGenBackend(cfg, params, hw=..., ...),
              own_backend=True)                         # offloaded
    for tok in llm.stream(prompt, max_new=64):          # incremental
        ...
    rid = llm.submit(prompt, max_new=16,
                     sampling=SamplingParams(kind="topp", top_p=0.9),
                     on_token=print)                    # callback stream
    llm.drain()

Requests are the unit: each carries its prompt, budget, stop token, and
its own :class:`repro.serving.sampling.SamplingParams` (per-request PRNG
stream included).  The facade owns the scheduler and picks the executor:

  * **one-shot generator** — when a ``generate`` call arrives with no
    other requests in flight and a rectangular prompt batch, the whole
    batch runs as one prefill + decode loop
    (:class:`repro.serving.engine.Generator` under the hood);
  * **continuous batcher** — ``submit``/``stream``, ragged prompts, or
    calls overlapping in-flight work run through slot-based continuous
    batching (:class:`repro.serving.batcher.ContinuousBatcher`).

Because sampling draws from request-owned PRNG streams (keyed by request
id and token count, never batch row), the two executors produce
token-identical output for the same requests — executor choice is purely
a throughput decision.

Backends plug in unchanged: ``backend=None`` serves the scan-stacked
resident path from ``params`` (or a jitted per-layer
``ResidentBackend`` when ``paged=True``); any
:class:`repro.serving.backends.LinearBackend` — including the phase-aware
:class:`repro.serving.backends.HeteGenBackend`, which swaps placement
plans between prefill and decode — drops in via ``backend=``.
``own_backend=True`` transfers backend lifetime to the facade;
``close()`` (or the context manager) tears down everything the facade
owns.

Scheduling is a facade-level knob (``policy="fcfs" | "priority" |
"fair_share"``, ``optimistic=``, ``preempt_mode=`` — see
:mod:`repro.serving.scheduler` and docs/SERVING.md): requests carry a
``priority``, page pressure preempts and resumes them token-exactly, and
``SamplingParams.logprobs`` records per-token log-probabilities in the
:class:`RequestOutput`.

:class:`AsyncLLM` is the event-loop front end over the same facade: a
background thread owns the ``step()`` crank, ``submit`` returns an
:class:`AsyncRequest` handle (awaitable-style ``result()`` + token
iterator), and ``stream()`` yields with no caller-driven stepping.
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.serving.batcher import ContinuousBatcher
from repro.serving.engine import Generator
from repro.serving.sampling import SamplingParams, request_key
from repro.serving.scheduler import SchedulerPolicy
from repro.serving.speculative import SpecConfig
from repro.serving.tokenizer import StreamDecoder, Tokenizer
from repro.telemetry.export import write_chrome_trace
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.overlap import OverlapReport, compute_overlap
from repro.telemetry.tracer import Tracer, as_tracer

Prompt = Sequence[int]


@dataclasses.dataclass
class GenRequest:
    """One generation request, fully self-describing."""

    prompt: List[int]
    max_new: int
    eos: Optional[int] = None
    sampling: SamplingParams = SamplingParams()
    stream: Optional[Callable[[int], None]] = None   # per-token callback
    rid: Optional[int] = None                        # assigned by the LLM
    priority: int = 0           # larger = more important (priority policy)


@dataclasses.dataclass
class RequestOutput:
    """What a finished request produced."""

    rid: int
    prompt: List[int]
    tokens: List[int]
    finish_reason: str          # "length" | "eos"
    # one entry per token when SamplingParams.logprobs was set:
    # {"token": id, "logprob": float, "top": {id: logprob, ...}}
    logprobs: Optional[List[Dict]] = None
    text: Optional[str] = None  # decoded tokens when the LLM has a tokenizer


def _finish_reason(tokens: List[int], eos: Optional[int]) -> str:
    return "eos" if (eos is not None and tokens and tokens[-1] == eos) \
        else "length"


class LLM:
    """Request-level serving facade — the one front door.

    ``cfg, params`` serve resident weights; ``backend=`` swaps the
    execution engine (ResidentBackend, HeteGenBackend, ...).  Scheduler
    shape (``max_slots``, ``max_len``, ``paged``, ``retune_hysteresis``,
    ...) is facade-level config; everything request-level travels on the
    request itself.
    """

    def __init__(self, cfg: ModelConfig, params: Optional[Dict] = None, *,
                 backend=None, own_backend: Optional[bool] = None,
                 sampling: SamplingParams = SamplingParams(),
                 max_slots: int = 4, max_len: int = 512,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 retune_hysteresis: Optional[int] = None,
                 policy: Union[str, SchedulerPolicy, None] = "fcfs",
                 optimistic: bool = True,
                 preempt_mode: Optional[str] = None,
                 chunk_tokens: Optional[int] = None,
                 prefix_dedupe: Optional[bool] = None,
                 spec: Optional[SpecConfig] = None,
                 tokenizer: Optional[Tokenizer] = None,
                 seed: int = 0,
                 selfcheck: bool = False,
                 trace: Union[bool, Tracer] = False,
                 wstream: Optional[str] = None):
        if backend is None and params is None:
            raise ValueError("LLM needs params or a backend")
        # ``wstream`` is a property of the offload backend (the resident
        # paths never stream weights): accept it here only as a cross-check
        # against the backend actually passed in, so a caller asking for
        # q8 streaming cannot silently get an fp (or resident) run.
        if wstream not in (None, "fp", "q8"):
            raise ValueError(f"unknown wire format {wstream!r} "
                             "(expected 'fp' or 'q8')")
        if wstream is not None:
            be_ws = getattr(backend, "wstream", None)
            if be_ws is None:
                if wstream != "fp":
                    raise ValueError(
                        "wstream='q8' needs a streaming backend "
                        "(HeteGenBackend(wstream='q8')); this backend does "
                        "not stream weights")
            elif be_ws != wstream:
                raise ValueError(
                    f"wstream={wstream!r} conflicts with the backend's "
                    f"wire format {be_ws!r}")
        self.wstream = wstream
        self.cfg = cfg
        self._params = params
        self._backend = backend
        built_here = False
        if backend is None and paged:
            # the scan-stacked cache is not pageable; paged resident
            # serving runs through the jitted per-layer backend
            from repro.serving.backends import ResidentBackend
            self._backend = ResidentBackend(cfg, params)
            built_here = True
        self._own_backend = built_here if own_backend is None \
            else bool(own_backend)
        self.sampling = sampling
        self.max_slots = max_slots
        self.max_len = max_len
        self.paged = paged
        self.page_size = page_size
        self.n_pages = n_pages
        self.kv_dtype = kv_dtype
        self.retune_hysteresis = retune_hysteresis
        self.policy = policy
        self.optimistic = optimistic
        self.preempt_mode = preempt_mode
        self.chunk_tokens = chunk_tokens
        self.prefix_dedupe = prefix_dedupe
        self.spec = spec
        self.tokenizer = tokenizer
        self.seed = seed
        # selfcheck: PagedKVCache(check=True) — validate allocator
        # invariants every step and audit for leaked pages at close
        self.selfcheck = selfcheck
        # observability (docs/OBSERVABILITY.md): trace=True records
        # zero-sync spans across the whole stack (batcher steps, engine
        # streams, scheduler events); the registry is always live and
        # merges the legacy stats() keys on metrics()
        self.tracer = as_tracer(trace)
        self._metrics = MetricsRegistry()
        if self.tracer and backend is not None \
                and hasattr(backend, "set_tracer"):
            backend.set_tracer(self.tracer)
        # lint: allow[prng-discipline] the facade's base key: request_key
        # folds per-request ids into it, step_key derives per-token draws
        self._base_key = jax.random.PRNGKey(seed)
        self._ids = itertools.count()
        self._batcher: Optional[ContinuousBatcher] = None
        self._generator: Optional[Generator] = None
        self._callbacks: Dict[int, Callable[[int], None]] = {}
        self._delivered: Dict[int, int] = {}
        self._streaming: set = set()    # rids owned by live stream() iters
        self._closed = False
        self.last_executor: Optional[str] = None
        self.last_metrics: Dict[str, float] = {}

    # -- executors ------------------------------------------------------
    def _ensure_batcher(self) -> ContinuousBatcher:
        if self._batcher is None:
            kw = dict(max_slots=self.max_slots, max_len=self.max_len,
                      seed=self.seed, paged=self.paged,
                      page_size=self.page_size, n_pages=self.n_pages,
                      kv_dtype=self.kv_dtype,
                      retune_hysteresis=self.retune_hysteresis,
                      policy=self.policy, optimistic=self.optimistic,
                      preempt_mode=self.preempt_mode,
                      chunk_tokens=self.chunk_tokens,
                      prefix_dedupe=self.prefix_dedupe,
                      spec=self.spec, selfcheck=self.selfcheck,
                      tracer=self.tracer, metrics=self._metrics)
            if self._backend is None:
                self._batcher = ContinuousBatcher(self.cfg, self._params,
                                                  **kw)
            else:
                # the facade manages backend lifetime, not the batcher
                self._batcher = ContinuousBatcher(self.cfg,
                                                  backend=self._backend,
                                                  own_backend=False, **kw)
        return self._batcher

    def _ensure_generator(self) -> Generator:
        if self._generator is None:
            if self._backend is None:
                self._generator = Generator(self.cfg, self._params)
            else:
                self._generator = Generator(self.cfg,
                                            backend=self._backend)
        return self._generator

    # -- request normalization -----------------------------------------
    def _encode(self, text: str) -> List[int]:
        if self.tokenizer is None:
            raise ValueError("text prompts need a tokenizer "
                             "(LLM(..., tokenizer=ByteTokenizer()))")
        return list(self.tokenizer.encode(text))

    def _decode(self, tokens: Sequence[int]) -> Optional[str]:
        return None if self.tokenizer is None \
            else self.tokenizer.decode(tokens)

    def _default_eos(self, eos: Optional[int]) -> Optional[int]:
        if eos is None and self.tokenizer is not None:
            return self.tokenizer.eos_id
        return eos

    def _as_requests(self, prompts, max_new, eos, sampling
                     ) -> List[GenRequest]:
        if isinstance(prompts, (GenRequest, str)):
            prompts = [prompts]
        elif prompts and isinstance(prompts[0], (int, np.integer)):
            prompts = [prompts]          # a single raw token sequence
        eos = self._default_eos(eos)
        reqs: List[GenRequest] = []
        for i, p in enumerate(prompts):
            if isinstance(p, GenRequest):
                req = p
            else:
                if max_new is None:
                    raise ValueError("max_new is required for raw prompts")
                sp = sampling[i] if isinstance(sampling, (list, tuple)) \
                    else (sampling or self.sampling)
                toks = self._encode(p) if isinstance(p, str) \
                    else list(int(t) for t in p)
                req = GenRequest(toks, max_new, eos=eos, sampling=sp)
            if req.rid is None:
                req.rid = next(self._ids)
            reqs.append(req)
        return reqs

    # -- blocking batch -------------------------------------------------
    def generate(self,
                 prompts: Union[Prompt, Sequence[Prompt],
                                Sequence[GenRequest]],
                 max_new: Optional[int] = None, *,
                 eos: Optional[int] = None,
                 sampling: Union[SamplingParams,
                                 Sequence[SamplingParams], None] = None
                 ) -> List[RequestOutput]:
        """Run a batch of requests to completion and return their outputs.

        Executor selection: a rectangular batch with nothing else in
        flight runs one-shot (single prefill + jitted decode loop);
        ragged prompts, per-request budgets, or overlap with submitted
        work run through the continuous batcher.  Either way the tokens
        are identical (request-owned sampling streams).
        """
        reqs = self._as_requests(prompts, max_new, eos, sampling)
        if not reqs:
            return []
        busy = self._batcher is not None and (
            self._batcher.queue or self._batcher.scheduler.resident())
        rect = (len({len(r.prompt) for r in reqs}) == 1
                and len({r.max_new for r in reqs}) == 1
                and not any(r.stream for r in reqs)
                # logprob extraction rides the batcher's sampler
                and not any(r.sampling.logprobs is not None for r in reqs)
                # speculative decoding is a batcher feature (draft →
                # verify → rollback lives in its step loop)
                and self.spec is None
                # block-pattern hybrids keep their Mamba state in the
                # batcher's paged cache; the one-shot path has none
                and not self.cfg.block_pattern)
        if rect and not busy:
            return self._generate_oneshot(reqs)
        return self._generate_batched(reqs)

    def _generate_oneshot(self, reqs: List[GenRequest]
                          ) -> List[RequestOutput]:
        g = self._ensure_generator()
        toks = jnp.asarray([r.prompt for r in reqs], jnp.int32)
        keys = [request_key(self._base_key, r.rid, r.sampling)
                for r in reqs]
        res = g.generate({"tokens": toks}, reqs[0].max_new,
                         sampling=[r.sampling for r in reqs],
                         request_keys=keys)
        self.last_executor = "generator"
        self.last_metrics = {"prefill_s": res.prefill_s,
                             "decode_s": res.decode_s,
                             "tokens_per_s": res.tokens_per_s}
        outs = []
        for req, row in zip(reqs, res.tokens):
            if req.eos is not None and req.eos in row:
                row = row[:row.index(req.eos) + 1]
            outs.append(RequestOutput(req.rid, req.prompt, list(row),
                                      _finish_reason(row, req.eos),
                                      text=self._decode(row)))
        return outs

    def _generate_batched(self, reqs: List[GenRequest]
                          ) -> List[RequestOutput]:
        b = self._ensure_batcher()
        for req in reqs:
            self._submit_req(req)
        t0 = time.perf_counter()
        steps = 0
        while not all(b.requests[r.rid].done for r in reqs):
            self._step_or_stall()
            steps += 1
        dt = max(time.perf_counter() - t0, 1e-9)
        n_tok = sum(len(b.requests[r.rid].generated) for r in reqs)
        self.last_executor = "batcher"
        self.last_metrics = {"steps": steps, "wall_s": dt,
                             "tokens_per_s": n_tok / dt}
        return [self._take_result(r.rid) for r in reqs]

    # -- incremental ----------------------------------------------------
    def submit(self, prompt: Union[Prompt, GenRequest],
               max_new: Optional[int] = None, *,
               eos: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               priority: Optional[int] = None,
               on_token: Optional[Callable[[int], None]] = None) -> int:
        """Queue one request on the continuous batcher; returns its id.

        ``on_token`` (or ``GenRequest.stream``) is called with each new
        token as scheduler steps deliver it.  ``priority`` matters to
        priority-aware scheduler policies (docs/SERVING.md); when given
        it overrides a ``GenRequest``'s own priority (0 included).
        """
        req = self._as_requests(prompt, max_new, eos, sampling)[0]
        if priority is not None:
            req.priority = priority
        return self._submit_req(req, on_token)

    def _submit_req(self, req: GenRequest,
                    on_token: Optional[Callable[[int], None]] = None
                    ) -> int:
        b = self._ensure_batcher()
        b.submit(req.prompt, req.max_new, req.eos,
                 sampling=req.sampling, rid=req.rid,
                 priority=req.priority)
        self._delivered[req.rid] = 0
        cb = on_token or req.stream
        if cb is not None:
            self._callbacks[req.rid] = cb
        return req.rid

    def step(self) -> int:
        """Advance the scheduler one decode step; fires stream callbacks.

        Returns the number of active slots after the step.
        """
        if self._batcher is None:
            return 0
        n = self._batcher.step()
        self._deliver()
        return n

    def _step_or_stall(self) -> int:
        """One scheduler step that refuses to spin: an idle scheduler
        whose admission makes no progress can never make any (a queued
        request wants more pages than the whole pool holds).  A resident
        slot mid-chunked-prefill counts as progress even though it is not
        decoding yet (step() legitimately returns 0 active slots then)."""
        b = self._batcher
        idle_before = not b.active.any() and not b.scheduler.resident()
        queued_before = len(b.queue)
        n = self.step()
        if n == 0 and b.queue and idle_before \
                and len(b.queue) == queued_before \
                and not b.scheduler.resident():
            raise RuntimeError("scheduler stalled with queued requests")
        return n

    def stream(self, prompt: Union[Prompt, GenRequest],
               max_new: Optional[int] = None, *,
               eos: Optional[int] = None,
               sampling: Optional[SamplingParams] = None
               ) -> Iterator[int]:
        """Submit one request and yield its tokens as they are decoded.

        Submission happens eagerly (the request is in the scheduler the
        moment this returns); only the token delivery is lazy.  Other
        in-flight requests keep advancing underneath (continuous
        batching); interleave several ``stream`` iterators freely.
        """
        rid = self.submit(prompt, max_new, eos=eos, sampling=sampling)
        # the iterator owns this request's reporting: a concurrent drain()
        # must neither evict it mid-iteration nor double-report it
        self._streaming.add(rid)
        return self._stream_tokens(rid)

    def _stream_tokens(self, rid: int) -> Iterator[int]:
        b = self._batcher
        req = b.requests[rid]
        sent = 0
        try:
            while True:
                while sent < len(req.generated):
                    yield req.generated[sent]
                    sent += 1
                if req.done:
                    break
                self._step_or_stall()
            self.last_executor = "batcher"
        finally:
            self._streaming.discard(rid)
            if req.done:
                self._take_result(rid)  # evict: fully delivered by yield

    def stream_text(self, prompt: Union[str, Prompt, GenRequest],
                    max_new: Optional[int] = None, *,
                    eos: Optional[int] = None,
                    sampling: Optional[SamplingParams] = None
                    ) -> Iterator[str]:
        """:meth:`stream`, decoded: yields text chunks as tokens land.

        Multi-byte characters that straddle token boundaries are held
        back until complete (empty chunks are skipped), so the
        concatenation of the yields is exactly ``decode(tokens)`` minus
        a trailing eos byte."""
        if self.tokenizer is None:
            raise ValueError("stream_text needs a tokenizer")
        dec = StreamDecoder(self.tokenizer)
        eos = self._default_eos(eos)
        for tok in self.stream(prompt, max_new, eos=eos,
                               sampling=sampling):
            if eos is not None and tok == eos:
                break
            chunk = dec.push(tok)
            if chunk:
                yield chunk
        tail = dec.flush()
        if tail:
            yield tail

    def drain(self, max_steps: int = 100_000) -> Dict[int, RequestOutput]:
        """Run the batcher until every submitted request finishes.

        Each finished request is reported exactly once (across drains and
        ``generate`` calls) and then evicted from the scheduler's books.
        """
        b = self._batcher
        if b is None:
            return {}
        t0 = time.perf_counter()
        before = sum(len(r.generated) for r in b.requests.values())
        steps = 0
        for _ in range(max_steps):
            if not b.queue and not b.scheduler.resident():
                break
            self._step_or_stall()
            steps += 1
        dt = max(time.perf_counter() - t0, 1e-9)
        toks = sum(len(r.generated) for r in b.requests.values()) - before
        self.last_executor = "batcher"
        self.last_metrics = {"steps": steps, "wall_s": dt,
                             "tokens_per_s": toks / dt}
        return {rid: self._take_result(rid)
                for rid in list(b.requests)
                if b.requests[rid].done and rid not in self._streaming}

    def result(self, rid: int) -> RequestOutput:
        """Output of a batcher-scheduled request (complete or partial)."""
        req = self._ensure_batcher().requests[rid]
        # the scheduler records why it finished a request; fall back to
        # inference for partial results (still running = "length" so far)
        reason = getattr(req, "finish_reason", None) \
            or _finish_reason(req.generated, req.eos)
        return RequestOutput(req.rid, req.prompt, list(req.generated),
                             reason,
                             logprobs=None if req.logprobs is None
                             else list(req.logprobs),
                             text=self._decode(req.generated))

    def _take_result(self, rid: int) -> RequestOutput:
        """result() + eviction: finished requests leave the scheduler's
        books once reported, so a long-lived facade doesn't accumulate
        every request it ever served (and repeated drains never re-report
        old work)."""
        out = self.result(rid)
        self._batcher.requests.pop(rid, None)
        self._delivered.pop(rid, None)
        return out

    def _deliver(self) -> None:
        for rid, cb in list(self._callbacks.items()):
            req = self._batcher.requests[rid]
            sent = self._delivered.get(rid, 0)
            for tok in req.generated[sent:]:
                cb(tok)
            self._delivered[rid] = len(req.generated)
            if req.done:
                del self._callbacks[rid]

    # -- introspection / lifecycle -------------------------------------
    @property
    def backend(self):
        """The executing backend (None = scan-stacked resident path)."""
        if self._backend is not None:
            return self._backend
        return self._batcher.backend if self._batcher is not None else None

    def stats(self) -> Dict:
        """Serving counters: executor choice, per-phase plans, engine
        stream busy-time — whatever the active backend exposes."""
        st: Dict = {"executor": self.last_executor, **self.last_metrics}
        be = self.backend
        if be is not None and hasattr(be, "wstream"):
            st["wstream"] = be.wstream
        if be is not None and hasattr(be, "policies"):
            st["phase_alpha"] = {ph: p.alpha
                                 for ph, p in be.policies.items()}
            st["phase_batch"] = {ph: (p.batch, p.tokens_per_seq)
                                 for ph, p in be.policies.items()}
        if be is not None and hasattr(be, "device_resident_bytes"):
            st["resident_bytes"] = be.device_resident_bytes()
        if be is not None and hasattr(be, "finish_stats"):
            st["stream"] = be.finish_stats()
        if self._batcher is not None:
            st["retunes"] = self._batcher.retunes
            sched = self._batcher.scheduler
            st["scheduler"] = {"policy": sched.policy.name,
                               "preemptions": sched.preemptions,
                               "waiting": len(sched.waiting),
                               "preempted": len(sched.preempted),
                               "chunks_planned": sched.chunks_planned,
                               "dedupe_hits": sched.dedupe_hits,
                               "dedupe_tokens": sched.dedupe_tokens,
                               # the current queue's worst holdup — the
                               # starvation signal a fairness/aging
                               # policy keys off
                               "max_wait_steps": max(
                                   (s.wait_steps for s in sched.pending),
                                   default=0)}
            kv = self._batcher.kv
            if kv is not None:
                st["paged"] = {"page_size": kv.page_size,
                               "pool_pages": kv.n_pages - 1,
                               "mapped_pages": kv.n_pages - 1
                               - kv.free_pages}
                # allocator self-check counters (cheap even without
                # check=True): pages_leaked != 0 means ref-count drift
                st["kv"] = kv.stats()
            if self._batcher.spec is not None:
                spec = self._batcher.spec_stats.as_dict()
                spec["per_request"] = {
                    rid: s.as_dict()
                    for rid, s in self._batcher.spec_by_req.items()}
                st["spec"] = spec
        return st

    def metrics(self) -> Dict:
        """One flat snapshot of every serving metric: the live batcher
        instruments (``serve.*``) merged with the legacy :meth:`stats`
        keys as namespaced gauges (``scheduler.preemptions``,
        ``kv.free_pages``, ``stream.cpu_s``, ...).  The nested
        :meth:`stats` dict remains during the deprecation window; this
        is its replacement surface (docs/OBSERVABILITY.md)."""
        reg = self._batcher.metrics if self._batcher is not None \
            else self._metrics
        reg.absorb(self.stats())
        return reg.snapshot()

    def write_trace(self, path: str) -> Dict:
        """Dump the recorded spans as Chrome trace JSON; returns the
        document (empty trace if tracing was never enabled)."""
        return write_chrome_trace(path, self.tracer)

    def overlap_report(self) -> OverlapReport:
        """Per-step I/O-hidden fraction / stream utilization / critical
        path from the recorded spans (paper Fig. 5c, Table 2)."""
        return compute_overlap(self.tracer.spans())

    def close(self) -> None:
        """Tear down everything the facade owns (idempotent)."""
        if self._closed:
            return
        self._closed = True
        if self._batcher is not None:
            self._batcher.close()
        if self._own_backend and self._backend is not None:
            self._backend.close()

    def __enter__(self) -> "LLM":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# AsyncLLM: the event-loop front end
# ---------------------------------------------------------------------------

_CLOSED = object()          # queue sentinel: no more tokens


class AsyncRequest:
    """Handle for a request submitted to :class:`AsyncLLM`.

    Iterate it to stream tokens as the background loop decodes them
    (blocking only while the next token is genuinely not ready), or call
    :meth:`result` to wait for the finished :class:`RequestOutput`.  Both
    are safe from any thread; the handle outlives the request inside the
    engine (tokens already queued keep flowing after completion)."""

    def __init__(self, rid: int):
        self.rid = rid
        self._q: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        self._output: Optional[RequestOutput] = None
        self._error: Optional[BaseException] = None

    # called by the AsyncLLM loop thread
    def _push(self, tok: int) -> None:
        self._q.put(tok)

    def _finish(self, output: Optional[RequestOutput] = None,
                error: Optional[BaseException] = None) -> None:
        self._output, self._error = output, error
        self._done.set()
        self._q.put(_CLOSED)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> RequestOutput:
        """Block until the request finished; the awaitable-style surface.

        Raises the loop's failure (scheduler stall, closed mid-flight)
        instead of returning a partial output."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.rid} still in flight")
        if self._error is not None:
            raise self._error
        return self._output

    def __iter__(self) -> Iterator[int]:
        while True:
            item = self._q.get()
            if item is _CLOSED:
                # keep the sentinel so a second iteration terminates too
                self._q.put(_CLOSED)
                if self._error is not None:
                    raise self._error
                return
            yield item


class AsyncLLM:
    """Event-loop serving: a background thread drives the scheduler.

    The synchronous :class:`LLM` only makes progress when the caller
    hand-cranks ``step()``; this front end owns that crank.  ``submit``
    returns an :class:`AsyncRequest` immediately and the loop thread
    steps the scheduler whenever requests are in flight — ``stream()``
    yields tokens with no caller-driven stepping, ``result()`` blocks
    like an awaitable, and many threads can submit/consume concurrently
    (the facade is guarded by one lock; decode steps batch work from
    every submitter).

        with AsyncLLM(cfg, params, policy="priority") as allm:
            hi = allm.submit(p1, max_new=32, priority=5)
            for tok in allm.stream(p2, max_new=64):   # no step() anywhere
                ...
            out = hi.result()

    Construction forwards every keyword to :class:`LLM` (policies, paged
    KV, backends, ...), or wraps an existing facade via ``llm=`` —
    ``close()`` tears down whatever it built.  ``close(drain=True)`` (the
    default) finishes in-flight requests first; ``close(drain=False)``
    abandons them, failing their handles with a ``RuntimeError``.  A
    scheduler failure (e.g. a stalled page pool) fails every in-flight
    handle and surfaces on the next ``submit``."""

    def __init__(self, cfg: Optional[ModelConfig] = None,
                 params: Optional[Dict] = None, *,
                 llm: Optional[LLM] = None, **llm_kwargs):
        if llm is None:
            llm = LLM(cfg, params, **llm_kwargs)
            self._own_llm = True
        else:
            if llm_kwargs or cfg is not None or params is not None:
                raise ValueError("pass either llm= or LLM constructor "
                                 "arguments, not both")
            self._own_llm = False
        self._llm = llm
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._handles: Dict[int, AsyncRequest] = {}
        self._closed = False
        self._failure: Optional[BaseException] = None
        self._busy_s = 0.0          # loop seconds spent inside step()
        self._tokens_done = 0       # tokens of finished requests
        self._thread = threading.Thread(target=self._loop,
                                        name="asyncllm-step", daemon=True)
        self._thread.start()

    # -- submission -----------------------------------------------------
    def _register(self, req: GenRequest) -> AsyncRequest:
        if self._closed:
            raise RuntimeError("AsyncLLM is closed")
        if self._failure is not None:
            raise RuntimeError("AsyncLLM loop failed") from self._failure
        h = AsyncRequest(-1)
        if req.stream is None:
            on_tok = h._push
        else:
            # the GenRequest's own per-token callback keeps firing (from
            # the loop thread) alongside the handle's queue
            def on_tok(tok, _user=req.stream, _push=h._push):
                _user(tok)
                _push(tok)
        h.rid = self._llm._submit_req(req, on_token=on_tok)
        self._handles[h.rid] = h
        return h

    def submit(self, prompt: Union[Prompt, GenRequest],
               max_new: Optional[int] = None, *,
               eos: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               priority: Optional[int] = None) -> AsyncRequest:
        """Queue one request; returns its handle immediately.  The
        background loop wakes and decodes without further calls."""
        with self._work:
            req = self._llm._as_requests(prompt, max_new, eos, sampling)[0]
            if priority is not None:
                req.priority = priority
            h = self._register(req)
            self._work.notify_all()
        return h

    def stream(self, prompt: Union[Prompt, GenRequest],
               max_new: Optional[int] = None, *,
               eos: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               priority: Optional[int] = None) -> Iterator[int]:
        """Submit and iterate tokens as the loop decodes them."""
        return iter(self.submit(prompt, max_new, eos=eos, sampling=sampling,
                                priority=priority))

    def generate(self,
                 prompts: Union[Prompt, Sequence[Prompt],
                                Sequence[GenRequest]],
                 max_new: Optional[int] = None, *,
                 eos: Optional[int] = None,
                 sampling: Union[SamplingParams,
                                 Sequence[SamplingParams], None] = None,
                 timeout: Optional[float] = None) -> List[RequestOutput]:
        """Blocking batch convenience over the event loop."""
        with self._work:
            reqs = self._llm._as_requests(prompts, max_new, eos, sampling)
            handles = [self._register(r) for r in reqs]
            self._work.notify_all()
        return [h.result(timeout) for h in handles]

    # -- the loop -------------------------------------------------------
    def _loop(self) -> None:
        while True:
            with self._work:
                while not self._handles and not self._closed:
                    self._work.wait()
                if not self._handles:          # closed and drained
                    return
                t0 = time.perf_counter()
                try:
                    self._llm._step_or_stall()
                except BaseException as e:     # stall, backend death, ...
                    self._failure = e
                    for h in self._handles.values():
                        h._finish(error=e)
                    self._handles.clear()
                    continue
                self._busy_s += time.perf_counter() - t0
                b = self._llm._batcher
                fin = [rid for rid in self._handles
                       if rid in b.requests and b.requests[rid].done]
                for rid in fin:
                    out = self._llm._take_result(rid)
                    self._tokens_done += len(out.tokens)
                    self._handles.pop(rid)._finish(output=out)

    # -- introspection / lifecycle -------------------------------------
    @property
    def llm(self) -> LLM:
        return self._llm

    def stats(self) -> Dict:
        with self._lock:
            st = self._llm.stats()
            st["in_flight"] = len(self._handles)
            if self._busy_s > 0:
                # the loop thread owns the crank, so the facade's
                # per-drain metrics never fire — report the loop's own
                st["executor"] = "batcher(async)"
                st["tokens_per_s"] = self._tokens_done / self._busy_s
            return st

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        """Stop the loop (idempotent).  ``drain=True`` lets in-flight
        requests finish first; ``drain=False`` abandons them — their
        handles raise ``RuntimeError`` from ``result()``/iteration.
        With a ``timeout``, raises ``TimeoutError`` if the drain did not
        finish in time — and leaves the backend open rather than tearing
        it down under the still-stepping loop thread."""
        with self._work:
            if not drain and self._handles:
                err = RuntimeError(
                    "AsyncLLM closed with requests in flight")
                for h in self._handles.values():
                    h._finish(error=err)
                self._handles.clear()
            self._closed = True
            self._work.notify_all()
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise TimeoutError(
                "AsyncLLM close timed out with requests still draining; "
                "retry close() or close(drain=False)")
        if self._own_llm:
            self._llm.close()

    def __enter__(self) -> "AsyncLLM":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
