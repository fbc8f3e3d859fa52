"""HeteGen runtime engine — threaded hybrid heterogeneous parallelism (§4.2).

Executes the linear modules of a model under a per-module placement plan:

    resident  — weights live in accelerator memory; plain device matmul.
    hetegen   — weights live in host memory; the output dimension is split
                at an MXU-tile-aligned column ``alpha``-fraction: the device
                part is staged (pin) || transferred (DMA) || the host part is
                computed by a host GEMM thread, all concurrently; results are
                concatenated (exact — column blocks of a matmul are
                independent).
    stream    — alpha = 1: pure weight streaming (FlexGen-style baseline).
    host      — alpha = 0: pure host compute (CPU-only baseline).

``wstream`` picks the wire format of the streamed device shards:

    "fp"      — stream the shard as-is (full precision).
    "q8"      — quantize each shard once at load to int8 + fp32 per-column
                scales (:func:`repro.kernels.q8_matmul.quantize_weights_np`)
                and stream the ``(q, scale)`` pair; the device share runs
                through :func:`repro.kernels.ops.q8_matmul`, dequantizing
                inside the matmul, so no fp copy of a streamed weight ever
                exists in device memory.  The host partition keeps its fp
                weights (it never crosses the link).  Pin/transfer spans
                carry the wire bytes (plus ``fp_bytes``, the uncompressed
                equivalent) so telemetry stays honest under compression.

Every fp share (resident, streamed, host) is held ``(out, in)``, its
columns as rows.  ``_host_matmul`` flattens the activation to one
``(M, K)`` matrix and runs one BLAS GEMM against its share, so a decode
step reads the share from memory once, not once per row of the batch
(a 3-D ``x @ w`` is a stack of one-row products).  The weights come in
as ``(in, out)`` arrays; where one is the view of an ``(out, in)``
array, as ``HeteGenBackend`` holds them, each share is a row slice of
it and the partition copies nothing.  Otherwise a share is copied into
that layout, except a whole weight left to the host, which stays a
view.  A q8 shard keeps the ``(in, out)`` layout its kernel reads.

Four real executors provide the four streams of the paper's Fig. 5c: the
host GEMM pool, the manager's pin thread, the transfer thread, and the
device queue (JAX async dispatch).  On a TPU the device is the chip and
``device_put`` is a host-to-device DMA.  On the CPU backend (tests) the
"device" is jax's CpuDevice, which shares the host's cores and
zero-copies ``device_put``; the mechanism — ordering, ring reuse,
prefetch, correctness — is the same, and per-stream busy seconds are
measured for the Table-2 style breakdown either way.

The transfer runs one linear ahead of the serving thread.  The engine
records the order in which its first pass calls the linears that have a
device share, wrapping from the last to the first; from then on each
such linear, once its own shard is in hand, sends the next one's DMA
(acquire its pinned slot, then transfer) to the transfer thread, so the
link runs while the serving thread moves activations, dispatches, and
does everything between linears.  At most one shard is in flight beyond
the linear being computed.  A call out of the predicted order is a miss:
its shard is fetched on demand and the one in flight waits for its own
call.  Weights do not depend on the input, so neither changes a result.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import alpha as alpha_lib
from repro.core.param_manager import (AsyncParamManager, Entry,
                                      entry_parts, plan_prefetch_order)
from repro.kernels import ops as kernel_ops
from repro.kernels.q8_matmul import quantize_weights_np
from repro.telemetry.tracer import NULL_TRACER, Tracer


@dataclasses.dataclass(frozen=True)
class ModulePlan:
    name: str
    group: str                 # size group for the pinned ring ("attn"/"mlp")
    mode: str                  # "resident" | "hetegen" | "stream" | "host"
    alpha: float = 1.0         # device fraction for hetegen


@dataclasses.dataclass
class StreamStats:
    cpu: float = 0.0           # host GEMM seconds
    pin: float = 0.0           # staging seconds
    trans: float = 0.0         # host->device transfer seconds
    dev: float = 0.0           # device matmul seconds
    wall: float = 0.0          # end-to-end engine-active seconds
    transfers_ahead: int = 0       # shard DMAs issued one linear ahead
    transfers_on_demand: int = 0   # shard DMAs issued by their own linear

    def utilization(self) -> Dict[str, float]:
        w = max(self.wall, 1e-12)
        return {"cpu": self.cpu / w, "pin": self.pin / w,
                "trans": self.trans / w, "dev": self.dev / w}

    def __add__(self, other: "StreamStats") -> "StreamStats":
        """Aggregate busy seconds across engines (e.g. the per-phase
        partitions of a phase-aware backend).  Wall takes the max: the
        engines share one serving timeline, they don't extend it."""
        return StreamStats(cpu=self.cpu + other.cpu,
                           pin=self.pin + other.pin,
                           trans=self.trans + other.trans,
                           dev=self.dev + other.dev,
                           wall=max(self.wall, other.wall),
                           transfers_ahead=(self.transfers_ahead
                                            + other.transfers_ahead),
                           transfers_on_demand=(self.transfers_on_demand
                                                + other.transfers_on_demand))


def engine_matmul(x, w):
    """The device share of a linear, its weight held ``(out, in)``
    (jitted; named so that its ops name it in a device profile)."""
    return x @ w.T


def engine_q8_matmul(x, q, s):
    """The device share of a linear streamed as int8 + per-column scales
    (jitted); prefill activations are (B, S, K), the kernel wants 2D."""
    y = kernel_ops.q8_matmul(x.reshape((-1, x.shape[-1])), q, s)
    return y.reshape(x.shape[:-1] + (q.shape[-1],))


def _row_major(a: np.ndarray) -> np.ndarray:
    """``a`` (2-D) as a C-contiguous array: itself when it is one, else a
    copy made 64 columns at a time.  When the copy transposes (a slice
    of a weight held the other way round), the blocks run several times
    faster than one ``np.ascontiguousarray``."""
    if a.flags.c_contiguous:
        return a
    out = np.empty(a.shape, a.dtype)
    for j in range(0, a.shape[1], 64):
        out[:, j:j + 64] = a[:, j:j + 64]
    return out


class HeteGenEngine:
    """Executes named linears under a placement plan with async overlap.

    Every blocking call :meth:`linear` makes on the serving thread sits in
    a span on the ``wait`` track, named for what it waits on
    (``act_to_host``, ``pin``, ``transfer``, ``device_sync``,
    ``host_gemm``) and carrying ``module`` and ``phase``."""

    def __init__(self, weights: Dict[str, np.ndarray],
                 plan: Sequence[ModulePlan], *,
                 biases: Optional[Dict[str, np.ndarray]] = None,
                 tile: int = 128,
                 device: Optional[jax.Device] = None,
                 resident_store: Optional[Dict[str, jax.Array]] = None,
                 tracer: Tracer = NULL_TRACER,
                 trace_phase: Optional[str] = None,
                 wstream: str = "fp"):
        if wstream not in ("fp", "q8"):
            raise ValueError(f"unknown wire format {wstream!r} "
                             "(expected 'fp' or 'q8')")
        self.plan = {p.name: p for p in plan}
        self.order = [p.name for p in plan]
        self.tile = tile
        self.device = device or jax.devices()[0]
        self.biases = {k: jnp.asarray(v) for k, v in (biases or {}).items()}
        self.stats = StreamStats()
        self._lock = threading.Lock()
        self.tracer = tracer
        self.trace_phase = trace_phase
        self.wstream = wstream

        # Partition every weight once, ahead of time.  ``resident_store``
        # lets a phase-aware backend run several engines (one partition per
        # serving phase) without holding duplicate device copies of the
        # modules both plans promote to residency.
        self._resident: Dict[str, jax.Array] = {}
        self._host_part: Dict[str, np.ndarray] = {}    # (out, in)
        self._dev_cols: Dict[str, int] = {}
        self._fp_shard_bytes: Dict[str, int] = {}   # uncompressed shard size
        stage_src: Dict[str, Entry] = {}
        groups: Dict[str, str] = {}
        for p in plan:
            w = weights[p.name]
            if p.mode == "resident":
                if resident_store is not None and p.name in resident_store:
                    self._resident[p.name] = resident_store[p.name]
                else:
                    self._resident[p.name] = jax.device_put(
                        _row_major(w.T), self.device)
                    if resident_store is not None:
                        resident_store[p.name] = self._resident[p.name]
                continue
            a = {"stream": 1.0, "host": 0.0}.get(p.mode, p.alpha)
            cols = alpha_lib.split_columns(a, w.shape[-1], tile)
            self._dev_cols[p.name] = cols
            if cols > 0:
                dev = w[:, :cols]
                self._fp_shard_bytes[p.name] = dev.nbytes
                # contiguous, so staging is a single memcpy
                if wstream == "q8":
                    # one-time load cost: the shard streams as int8
                    # payload + fp32 per-column scales from here on, in
                    # the (in, out) layout the q8 kernel reads
                    q, scale = quantize_weights_np(dev)
                    stage_src[p.name] = (_row_major(q), scale)
                else:
                    stage_src[p.name] = _row_major(dev.T)
                groups[p.name] = p.group
            if cols < w.shape[-1]:
                host = w[:, cols:].T
                self._host_part[p.name] = _row_major(host) if cols else host
        # host memory the partition holds apart from the caller's weights
        # (a share already laid out (out, in) is a view, no copy)
        held = [(n, a) for n, src in stage_src.items()
                for a in entry_parts(src)] + list(self._host_part.items())
        self.host_bytes_copied = sum(
            0 if np.may_share_memory(a, weights[n]) else a.nbytes
            for n, a in held)

        self.manager = (AsyncParamManager(stage_src, groups,
                                          tracer=tracer,
                                          trace_phase=trace_phase,
                                          fp_bytes=self._fp_shard_bytes)
                        if stage_src else None)
        self._next_in_group = plan_prefetch_order(
            [n for n in self.order if n in stage_src], groups)

        # look-ahead transfer: the first pass's call order of the linears
        # with a device share, closed into a ring on its first repeat or
        # at the next step boundary (:meth:`warm_prefetch`), and the one
        # shard in flight beyond the current linear
        self._calls: Dict[str, None] = {}
        self._next_call: Optional[Dict[str, str]] = None
        self._ahead: Optional[Tuple[str, Future]] = None
        # jax's CPU backend zero-copies device_put, so a slot there stays
        # held until the device matmul has read it; any other device has
        # its own copy once the DMA completes
        self._release_on_dma = self.device.platform != "cpu"

        self._cpu_pool = ThreadPoolExecutor(max_workers=1,
                                            thread_name_prefix="hostgemm")
        self._trans_pool = ThreadPoolExecutor(max_workers=1,
                                              thread_name_prefix="transfer")

        self._matmul = jax.jit(engine_matmul)
        self._q8_matmul = jax.jit(engine_q8_matmul)
        self._t_start = time.perf_counter()

    # ------------------------------------------------------------------
    def warm_prefetch(self) -> None:
        """Stage the first module of each group before the step begins,
        and, once the call order is known, send the DMA of the step's
        first linear ahead.  Called between steps, it closes the first
        pass's call order."""
        if self.manager is None:
            return
        seen = set()
        for name in self.order:
            p = self.plan[name]
            if name in self._dev_cols and self._dev_cols[name] > 0 \
                    and p.mode in ("hetegen", "stream"):
                if p.group not in seen:
                    self.manager.prefetch(name)
                    seen.add(p.group)
        if self._next_call is None and self._calls:
            self._close_order()
        if self._next_call is not None:
            self._issue_ahead(next(iter(self._calls)))

    def _close_order(self) -> None:
        """Fix the first pass's call order, the last linear followed by
        the first."""
        calls = list(self._calls)
        self._next_call = dict(zip(calls, calls[1:] + calls[:1]))

    def _learn_order(self, name: str) -> None:
        """Record ``name`` in the first pass's call order; the first
        linear called a second time closes it."""
        if self._next_call is not None:
            return
        if name in self._calls:
            self._close_order()
        else:
            self._calls[name] = None

    def _issue_ahead(self, name: str) -> None:
        """Send ``name``'s DMA to the transfer thread unless a shard is
        already in flight beyond the current linear."""
        if self._ahead is not None:
            return
        fut = self._trans_pool.submit(self._fetch, name, None)
        with self._lock:
            self.stats.transfers_ahead += 1
            self._ahead = (name, fut)

    def _shard(self, name: str) -> Future:
        """The future of ``name``'s shard: the one in flight if it was
        issued ahead for ``name``, else a DMA issued now, on demand."""
        if self._ahead is not None and self._ahead[0] == name:
            with self._lock:
                fut, self._ahead = self._ahead[1], None
            return fut
        buf = self.manager.acquire(name)
        with self._lock:
            self.stats.transfers_on_demand += 1
        return self._trans_pool.submit(self._fetch, name, buf)

    def _fetch(self, name: str, buf: Optional[Entry]
               ) -> Tuple[Entry, Optional[int]]:
        """The transfer task: ``name``'s shard on the device, and the pin
        sequence number it was staged under.  ``buf`` None means the DMA
        was issued ahead, so the task acquires the pinned slot itself."""
        ahead = buf is None
        if ahead:
            buf = self.manager.acquire_ahead(name)
        seq = self.manager.seq_of(name)
        w_dev = self._transfer(buf, name, seq, ahead)
        if self._release_on_dma:
            # the slot is free for the pin thread to stage the linear
            # after next while the serving thread computes
            self.manager.release(name)
            self.manager.prefetch(self._next_in_group.get(name))
        return w_dev, seq

    def _host_matmul(self, x_np: np.ndarray, name: str) -> np.ndarray:
        w = self._host_part[name]
        with self.tracer.span(name, track="cpu_gemm", bytes=w.nbytes,
                              module=name, phase=self.trace_phase):
            t0 = time.perf_counter()
            x2 = x_np.reshape((-1, x_np.shape[-1]))
            y = np.ascontiguousarray(
                (w @ x2.T).T.reshape(x_np.shape[:-1] + (w.shape[0],)))
            with self._lock:
                self.stats.cpu += time.perf_counter() - t0
        return y

    def _transfer(self, buf: Entry, name: str, seq: Optional[int],
                  ahead: bool) -> Entry:
        parts = buf if isinstance(buf, tuple) else (buf,)
        wire = sum(p.nbytes for p in parts)
        attrs = dict(bytes=wire, module=name, phase=self.trace_phase,
                     ahead=ahead)
        if seq is not None:
            attrs["seq"] = seq
        fp = self._fp_shard_bytes.get(name)
        if fp is not None:
            attrs["fp_bytes"] = fp
        with self.tracer.span(name, track="transfer", **attrs):
            t0 = time.perf_counter()
            arrs = tuple(jax.device_put(p, self.device) for p in parts)
            for a in arrs:
                # lint: allow[hot-path-sync] transfer-stream timing: the sync
                # is the measurement (trans busy-seconds feed the alpha law),
                # and it runs on the dedicated transfer thread, not the
                # dispatch thread
                a.block_until_ready()
            with self._lock:
                self.stats.trans += time.perf_counter() - t0
        return arrs if isinstance(buf, tuple) else arrs[0]

    # ------------------------------------------------------------------
    def _wait(self, kind: str, name: str):
        """A span on the ``wait`` track around one blocking call."""
        return self.tracer.span(kind, track="wait", module=name,
                                phase=self.trace_phase)

    def linear(self, x: jax.Array, name: str) -> jax.Array:
        """y = x @ W[name] (+ bias), executed per the placement plan."""
        p = self.plan[name]
        if p.mode == "resident":
            with self.tracer.span(name, track="device", module=name,
                                  phase=self.trace_phase):
                t0 = time.perf_counter()
                y = self._matmul(x, self._resident[name])
                with self._wait("device_sync", name):
                    # lint: allow[hot-path-sync] device-stream timing: dev
                    # busy-seconds are the alpha controller's input signal
                    y.block_until_ready()
                with self._lock:
                    self.stats.dev += time.perf_counter() - t0
        else:
            cols = self._dev_cols[name]
            has_host = name in self._host_part

            # 1. stage-ahead: kick the pin of the next same-group module
            if self.manager is not None and cols > 0:
                nxt = self._next_in_group.get(name)
                if nxt is not None:
                    self.manager.prefetch(nxt)

            # 2. host share on the GEMM thread (x moves device->host first,
            #    as in the paper: "transmitting activation from the GPU")
            host_fut = None
            if has_host:
                with self._wait("act_to_host", name):
                    # lint: allow[hot-path-sync] the paper's §4.2 activation
                    # move: the host GEMM share needs x on the CPU, and this
                    # transfer is what the alpha split already budgets for
                    x_np = np.asarray(x)
                host_fut = self._cpu_pool.submit(self._host_matmul, x_np, name)

            # 3. device share: its shard (in flight if issued ahead, else
            # acquired and sent now), then the next linear's DMA ahead,
            # then the matmul.  On the CPU backend the slot is released
            # only after the device matmul finished (zero-copy
            # device_put); elsewhere the transfer task released it.
            y_dev = None
            if cols > 0:
                self._learn_order(name)
                w_fut = self._shard(name)
                nxt = (self._next_call or {}).get(name)
                if nxt is not None and nxt != name:
                    self._issue_ahead(nxt)
                with self._wait("transfer", name):
                    w_dev, seq = w_fut.result()
                with self.tracer.span(name, track="device", module=name,
                                      phase=self.trace_phase, seq=seq):
                    t0 = time.perf_counter()
                    y_dev = (self._q8_matmul(x, *w_dev)
                             if isinstance(w_dev, tuple)
                             else self._matmul(x, w_dev))
                    with self._wait("device_sync", name):
                        # lint: allow[hot-path-sync] ring-slot release
                        # ordering: jax's CPU backend zero-copies
                        # device_put, so the read must finish before the
                        # slot is re-staged (see above)
                        y_dev.block_until_ready()
                    with self._lock:
                        self.stats.dev += time.perf_counter() - t0
                if not self._release_on_dma:
                    self.manager.release(name)

            # 4. combine
            if host_fut is None:
                y = y_dev
            else:
                with self._wait("host_gemm", name):
                    y_np = host_fut.result()
                y_host = jnp.asarray(y_np)
                y = y_host if y_dev is None else \
                    jnp.concatenate([y_dev, y_host], axis=-1)

        if name in self.biases:
            y = y + self.biases[name]
        return y

    # ------------------------------------------------------------------
    def set_tracer(self, tracer: Tracer,
                   trace_phase: Optional[str] = None) -> None:
        """Swap the tracer (and phase label) on a live engine — used when
        tracing is enabled after the engine was built."""
        self.tracer = tracer
        if trace_phase is not None:
            self.trace_phase = trace_phase
        if self.manager is not None:
            self.manager.tracer = tracer
            self.manager.trace_phase = self.trace_phase

    def finish_stats(self) -> StreamStats:
        with self._lock:
            self.stats.wall = time.perf_counter() - self._t_start
            if self.manager is not None:
                self.stats.pin = self.manager.pin_seconds
            return self.stats

    def reset_stats(self) -> None:
        with self._lock:
            self.stats = StreamStats()
            self._t_start = time.perf_counter()
        if self.manager is not None:
            self.manager.reset_pin_seconds()

    def device_resident_bytes(self) -> int:
        return sum(int(np.prod(w.shape)) * w.dtype.itemsize
                   for w in self._resident.values())

    def pinned_overhead_bytes(self) -> int:
        return 0 if self.manager is None else self.manager.pinned_overhead_bytes()

    def close(self) -> None:
        self._cpu_pool.shutdown(wait=True)
        self._trans_pool.shutdown(wait=True)
        with self._lock:
            self._ahead = None       # the in-flight shard's device copy
        if self.manager is not None:
            self.manager.shutdown()
