"""One run of one cell: build the system, warm it up, offer the load for
the window, and record what the clients saw.

The path under the window is the program's own serving stack::

    LLM -> paged ContinuousBatcher / Scheduler
      -> HeteGenBackend (per-phase plans) -> HeteGenEngine
      -> PagedKVCache -> paged_attention / paged_prefill

Requests go in through ``LLM.submit`` as ``GenRequest`` objects whose
``stream`` callback stamps every token on the client's clock
(``time.perf_counter``), and the harness turns the crank itself
(``LLM.step``) in the one thread that also plays the clients.  The
program's ``AsyncLLM`` is not used: its loop thread holds its lock
through each step and takes it again at once, so a client thread's
``submit`` waits until no request is running (PERF.md).  Sampling is
greedy and there is no stop token, so each request makes exactly the
number of tokens its traffic drew.

Every run also keeps, for each sampled row, the largest logit the program
computed there (one ``max`` over the row on the device, read back after
the window): under greedy sampling that is the program's own logit of the
token it served, which the correctness check compares with the reference.

With ``trace`` the harness also wraps its own calls into the layers —
backend prefill and decode, plan rebuilds, sampling — in
``jax.profiler.TraceAnnotation`` spans and records their shapes on the
host, turns the program's tracer on, and profiles the window.  Nothing
the program computes changes.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import collections
import math
import shutil
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.harness import traffic as traffic_lib

CACHE_DIR = "bench/.jax_cache"          # relative to the checkout
TRACE_DIR = "bench/.trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Record:
    """What one client saw of one request (host clock seconds)."""

    index: int
    prompt: List[int]
    max_new: int
    client: int = -1
    submit: Optional[float] = None       # when submit() returned
    rid: Optional[int] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    top_logits: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new


@dataclasses.dataclass
class LayerRecord:
    """Host-side records the harness takes at its own calls into the
    layers (traced runs only): (start, end, ...) per call."""

    decode: List = dataclasses.field(default_factory=list)    # rows, kv lens
    prefill: List = dataclasses.field(default_factory=list)   # b, s
    builds: List = dataclasses.field(default_factory=list)    # phase


def configure_jax(root: str) -> str:
    """Persistent compilation cache at a fixed path inside the checkout,
    every entry kept; float32 matrix products at full float32, as the
    configurations state (a TPU otherwise rounds float32 operands to
    bfloat16)."""
    import jax

    path = os.path.join(root, CACHE_DIR)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_default_matmul_precision", "highest")
    return path


def seed_key(seed: int):
    """The key every weight of a run is drawn from."""
    import jax

    return jax.random.PRNGKey(int(seed))


class Run:
    """Everything one run of one cell holds."""

    def __init__(self, bench, cell_name: str, seed: int, seconds: float,
                 trace: bool, *, t_start: float, log: Callable[[str], None],
                 require_tpu: bool = True):
        self.bench = bench
        self.cell = bench.cell(cell_name)
        self.conf = bench.config(self.cell["config"])
        self.arch = bench.arch(self.conf["arch"])
        self.counts = self.arch.counts(self.conf)
        self.mix = bench.traffic(self.cell["traffic"])
        self.limits = bench.limits(cell_name)
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.log = log
        self.require_tpu = require_tpu
        self.records: List[Record] = []
        self.layer = LayerRecord()
        self.taps: List = []            # (device row maxima, rids) per sample
        self.root = str(bench.root)

    # -- set-up ----------------------------------------------------------
    def device(self):
        import jax

        devices = jax.devices()
        dev = devices[0]
        info = {"platform": dev.platform, "kind": dev.device_kind,
                "count": len(devices)}
        self.log(f"device: {info}")
        if self.require_tpu:
            if dev.platform != "tpu":
                raise NoChip(f"JAX found platform {dev.platform!r}, not a TPU")
            if len(devices) < int(self.cell["chips"]):
                raise NoChip(f"{len(devices)} chips, the cell asks for "
                             f"{self.cell['chips']}")
            from repro.kernels import ops
            if ops._mode() != "pallas":
                raise NoChip("kernels would not run as Pallas kernels")
            self.peaks = self.bench.peaks(dev.device_kind)
        else:
            self.peaks = None
        self.dev = dev
        self.device_info = info
        return info

    def build(self):
        """Weights from the seed, the offload backend, the serving stack."""
        from repro.serving.api import LLM
        from repro.serving.backends import HeteGenBackend
        from repro.telemetry.tracer import Tracer

        conf, mix = self.conf, self.mix
        t0 = time.perf_counter()
        self.cfg = self.arch.program_config(conf)
        self.make_weights = self.arch.make_weights(conf)
        w = self.make_weights(seed_key(self.seed))
        params = self.arch.program_params(w)
        del w
        self.log(f"build: weights on the host at +{time.perf_counter() - t0:.3f} s")
        self.backend = HeteGenBackend(
            self.cfg, params, batch=int(mix["slots"]),
            budget_bytes=float(conf["budget_bytes"]), wstream=conf["wstream"])
        del params
        gc.collect()
        self.log(f"build: backend at +{time.perf_counter() - t0:.3f} s")
        ps = int(conf["page_size"])
        self.max_len = int(mix["prompt"]["max"]) + int(mix["output"]["max"])
        # every slot can hold its longest sequence, so the scheduler never
        # preempts (+ the trash page)
        n_pages = int(mix["slots"]) * math.ceil(self.max_len / ps) + 1
        self.tracer = Tracer(capacity=1 << 20) if self.trace else False
        self.llm = LLM(self.cfg, backend=self.backend, own_backend=True,
                       max_slots=int(mix["slots"]), max_len=self.max_len,
                       paged=True, page_size=ps, n_pages=n_pages,
                       chunk_tokens=int(mix["chunk_tokens"]),
                       trace=self.tracer)
        self.llm._ensure_batcher()
        self._tap_sampling()
        if self.trace:
            self._wrap_layers()
        self.log("plans: " + ", ".join(
            f"{ph} alpha={p.alpha:.4f}"
            for ph, p in sorted(self.backend.policies.items()))
            + f"; pool {n_pages - 1} pages of {ps}; max_len {self.max_len}")

    # -- the harness's own calls into the layers -------------------------
    def _tap_sampling(self):
        """Keep each sampled row's largest logit (on the device) with the
        request the row belongs to; in traced runs, annotate sampling."""
        import jax.numpy as jnp
        from repro.serving.scheduler import RUNNING

        b = self.llm._batcher
        inner = b._sample_slot_rows
        taps, trace = self.taps, self.trace

        def sample(logits, slots):
            slot_req = b.scheduler.slot_req
            rids = [st.rid if st is not None and st.status == RUNNING
                    else None for st in (slot_req[int(s)] for s in slots)]
            taps.append((jnp.max(logits, axis=-1), rids))
            if not trace:
                return inner(logits, slots)
            from jax.profiler import TraceAnnotation
            with TraceAnnotation("bench.sample"):
                return inner(logits, slots)

        b._sample_slot_rows = sample

    def _wrap_layers(self):
        from jax.profiler import TraceAnnotation

        be, rec = self.backend, self.layer
        inner_prefill, inner_decode = be.prefill, be.decode
        inner_retune = be.retune

        def prefill(batch, cache):
            b, s = batch["tokens"].shape
            t0 = time.perf_counter()
            with TraceAnnotation("bench.prefill"):
                out = inner_prefill(batch, cache)
            rec.prefill.append((t0, time.perf_counter(), int(b), int(s)))
            return out

        def decode(token, cache):
            sched = self.llm._batcher.scheduler
            lens = [st.kv_len + 1 for st in sched.running()]
            t0 = time.perf_counter()
            with TraceAnnotation("bench.decode"):
                out = inner_decode(token, cache)
            rec.decode.append((t0, time.perf_counter(), int(token.shape[0]),
                               lens))
            return out

        def retune(batch, phase="decode", **kw):
            before = be.engines.get(phase)
            t0 = time.perf_counter()
            with TraceAnnotation("bench.retune"):
                out = inner_retune(batch, phase, **kw)
            if be.engines.get(phase) is not before:
                rec.builds.append((t0, time.perf_counter(), phase))
            return out

        be.prefill, be.decode, be.retune = prefill, decode, retune

    # -- warm-up -----------------------------------------------------------
    def warm_up(self):
        """Run every prefill shape this cell's traffic can meet, through
        the synchronous facade so that each admission lands in a step of
        its own, as the load generator keeps it in the window:

        * the first prefill is of ``chunk_tokens / 2`` tokens, so the
          prefill plan (rebuilt only when a prefill's size leaves [1/2, 2]
          times the planned size) covers every single-prompt chunk of the
          mix and no plan is rebuilt for one in the window;
        * every prompt length up to the chunk, alone and as the tail of a
          chunked prompt.

        The decode batches are warmed by the clients' ramp (part of
        set-up, :meth:`offer_load`): requests join one at a time, so the
        batch passes through every size from 1 to the slot count.  The
        pool holds every slot's longest sequence, so no request is
        preempted and no swap shape needs warming.
        """
        rng = np.random.default_rng(self.seed + 1)
        vocab = self.cfg.vocab_size
        mix, llm = self.mix, self.llm
        t0 = time.perf_counter()
        self.log("warm-up: start")
        chunk = int(mix["chunk_tokens"])
        for n in [chunk // 2] + traffic_lib.warmup_prompt_lengths(mix):
            llm.submit([int(t) for t in rng.integers(0, vocab, n)], 2)
            llm.drain()
            self.log(f"warm-up: prompt of {n} done at "
                     f"+{time.perf_counter() - t0:.3f} s")

    # -- clients and the window ---------------------------------------------
    def offer_load(self) -> None:
        """Start the clients, open the window (``w0``, ``w1``), and keep
        every client sending its next request once its last one finished
        until the window closes: at the end of the first step that
        returns after ``seconds``.

        Requests go in one at a time: a client whose turn has come waits
        until the request sent before it has its first token, so no
        prefill ever holds two prompts.  The program has no bucketing of
        joint prefill shapes, and one of several rows would compile and
        re-plan inside the window (PERF.md)."""
        from repro.serving.api import GenRequest

        mix, llm = self.mix, self.llm
        reqs = traffic_lib.make_requests(mix, self.seed, self.cfg.vocab_size)
        self.records = [Record(r.index, r.prompt, r.max_new) for r in reqs]
        it = iter(self.records)
        waiting = collections.deque(range(int(mix["clients"])))
        finished: List[Record] = []
        last: List[Optional[Record]] = [None]

        def submit(client: int) -> None:
            rec = next(it, None)
            if rec is None:
                raise RuntimeError("the mix's decks ran out; give it more "
                                   "decks")

            def on_token(tok, rec=rec):
                rec.stamps.append(time.perf_counter())
                rec.tokens.append(int(tok))
                if len(rec.tokens) == rec.max_new:
                    finished.append(rec)

            rec.client = client
            rec.rid = llm.submit(GenRequest(list(rec.prompt), rec.max_new,
                                            stream=on_token))
            rec.submit = time.perf_counter()
            last[0] = rec

        def serve(until: Callable[[], bool], deadline: float,
                  admit: bool = True) -> None:
            while not until() and time.perf_counter() < deadline:
                prev = last[0]
                if admit and waiting and (prev is None or prev.tokens):
                    submit(waiting.popleft())
                llm._step_or_stall()
                for rec in finished:
                    llm._take_result(rec.rid)
                    waiting.append(rec.client)
                finished.clear()

        # the ramp: every client has sent a request and the last request
        # sent has decoded a token beside the others, so every decode
        # batch size the window can meet has run
        clients = int(mix["clients"])
        serve(lambda: (last[0] is not None and last[0].index >= clients - 1
                       and len(last[0].tokens) >= 2), float("inf"))
        self.log(f"ramp: done at +{time.perf_counter() - self.t_start:.3f} s")
        if self.trace:
            self._start_profiler()
        # the window: no step starts after its time is up, and it closes
        # when the step in flight then has returned, so every token counts
        # over all the time its step took (a step delivers a token to
        # every running request at once)
        self.w0 = time.perf_counter()
        serve(lambda: False, self.w0 + self.seconds)
        self.w1 = time.perf_counter()
        if self.trace:
            self._stop_profiler()
        # an answer that comes after the close is late, not wrong: with no
        # new request sent, those in flight go on until the check has
        # enough finished requests or the wait runs out (nothing after the
        # close is counted in a metric)
        want = int(self.limits.get("finished_at_least", 0))
        serve(lambda: sum(r.done for r in self.records) >= want,
              time.perf_counter() + float(self.limits.get("wait_s", 0)),
              admit=False)
        self.t_drained = time.perf_counter()

    def _start_profiler(self):
        import jax
        from jax.profiler import TraceAnnotation

        self.trace_dir = os.path.join(self.root, TRACE_DIR)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        with TraceAnnotation("bench.clock_sync"):
            self.t_sync = time.perf_counter()

    def _stop_profiler(self):
        import jax

        jax.profiler.stop_trace()

    def close(self):
        """Stop the load; read the device's peak memory; free the program;
        hand each request the program's top logits of its served tokens."""
        self.spans = self.tracer.spans() if self.tracer else []
        self.events = self.tracer.events_list() if self.tracer else []
        self.llm.close()
        stats = self.dev.memory_stats() or {}
        self.memory_peak_bytes = stats.get("peak_bytes_in_use")
        by_rid: Dict[int, List[float]] = {}
        for top, rids in self.taps:
            vals = np.asarray(top, np.float32)
            for v, rid in zip(vals, rids):
                if rid is not None:
                    by_rid.setdefault(rid, []).append(float(v))
        for r in self.records:
            r.top_logits = by_rid.get(r.rid, [])[:len(r.tokens)]
        self.taps.clear()
        del self.llm, self.backend
        gc.collect()
