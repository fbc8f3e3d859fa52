"""Asynchronous parameter manager (paper §4.3, Fig. 6).

Hybrid heterogeneous parallelism needs every streamed module's weights to be
*pinned* (staged into a DMA-able buffer) before its transfer starts.  The
manager guarantees:

  * asynchrony — pinning of the *next* module in a size group overlaps the
    current module's compute/transfer (the preceding module "prepares the
    pinned weights for the subsequent parameters");
  * bounded memory — at most one spare pinned parameter per group: each
    group owns a ring of two fixed slots (consume one while staging the
    other), sized to the group's largest member.  Groups exist because
    within a group module sizes are uniform, so pin times are uniform and
    no bubbles form (paper: linears-in-attention vs linears-in-MLP).

On a TPU host "pinning" is the staging memcpy into the DMA ring
(DESIGN.md §2); here it is a real ``np.copyto`` into a preallocated buffer,
executed by a dedicated pin thread, so overlap and ordering are real even
though the container is CPU-only.

A module's entry may be a single array or a **tuple of arrays** (the
quantized wire format streams an int8 payload plus its fp32 per-column
scales): tuple parts are packed sequentially into one slot and come back
as typed views, so rings are sized to the *wire* bytes actually staged —
compressed formats shrink the pinned footprint for free.  Pin spans carry
those wire bytes (plus ``fp_bytes``, the uncompressed equivalent, when
the owner supplies it) and a per-module ``seq`` counter that the engine
re-stamps on the matching transfer/device spans, so the trace shows which
pin fed which transfer (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.telemetry.tracer import NULL_TRACER, Tracer

# one staged entry: a host array, or parts packed into one slot
Entry = Union[np.ndarray, Tuple[np.ndarray, ...]]

_ALIGN = 64      # part offsets inside a slot (keeps typed views aligned)


def entry_parts(entry: Entry) -> Tuple[np.ndarray, ...]:
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


def entry_wire_bytes(entry: Entry) -> int:
    """Bytes this entry moves over pin/DMA — the sum of its parts."""
    return sum(p.nbytes for p in entry_parts(entry))


def entry_slot_bytes(entry: Entry) -> int:
    """Staging bytes the entry occupies (parts padded to alignment)."""
    off = 0
    for p in entry_parts(entry):
        off = -(-off // _ALIGN) * _ALIGN + p.nbytes
    return off


@dataclasses.dataclass
class PinSlot:
    buffer: np.ndarray                    # preallocated staging memory
    name: Optional[str] = None            # module currently staged
    ready: Optional[Future] = None        # resolves when staging completes
    in_use: bool = False                  # acquired and not yet released
    seq: int = -1                         # per-module pin sequence number


class GroupRing:
    """Two-slot staging ring for one size group."""

    def __init__(self, group: str, slot_bytes: int):
        self.group = group
        self.slot_bytes = slot_bytes
        self.slots = [PinSlot(np.empty(slot_bytes, dtype=np.uint8))
                      for _ in range(2)]
        self.lock = threading.Condition()

    def slot_for(self, name: str) -> Optional[PinSlot]:
        for s in self.slots:
            if s.name == name:
                return s
        return None

    def free_slot(self) -> Optional[PinSlot]:
        for s in self.slots:
            if not s.in_use and s.ready is None:
                return s
        return None


class AsyncParamManager:
    """Stages module weights into pinned rings ahead of use.

    Typical engine driving pattern (paper Fig. 6)::

        mgr.prefetch(first_module_of_each_group)
        for module in plan:
            mgr.prefetch(next_same_group_module(module))   # stage ahead
            buf = mgr.acquire(module)                      # wait if needed
            ... transfer buf, compute ...
            mgr.release(module)
    """

    def __init__(self, weights: Dict[str, Entry],
                 groups: Dict[str, str], *,
                 tracer: Tracer = NULL_TRACER,
                 trace_phase: Optional[str] = None,
                 fp_bytes: Optional[Dict[str, int]] = None):
        """``weights``: host arrays (or part tuples) per module;
        ``groups``: module -> group.  ``fp_bytes`` optionally maps a
        module to the uncompressed byte count its entry represents —
        stamped on pin spans so trace consumers can relate wire traffic
        back to compute bytes."""
        self.weights = weights
        self.groups = groups
        self.tracer = tracer
        self.trace_phase = trace_phase
        self.fp_bytes = fp_bytes or {}
        by_group: Dict[str, List[str]] = {}
        for name, g in groups.items():
            by_group.setdefault(g, []).append(name)
        self.rings: Dict[str, GroupRing] = {}
        for g, names in by_group.items():
            slot_bytes = max(entry_slot_bytes(weights[n]) for n in names)
            self.rings[g] = GroupRing(g, slot_bytes)
        self._seq: Dict[str, int] = {}    # per-module pin counter
        self._pinner = ThreadPoolExecutor(max_workers=1,
                                          thread_name_prefix="pin")
        # accumulated by the pin thread, read/reset by the engine thread —
        # guarded the same way HeteGenEngine.stats is
        self._pin_lock = threading.Lock()
        self._pin_seconds = 0.0

    # ------------------------------------------------------------------
    def _do_pin(self, slot: PinSlot, name: str, seq: int) -> Entry:
        src = self.weights[name]
        parts = entry_parts(src)
        attrs = dict(bytes=entry_wire_bytes(src), module=name,
                     phase=self.trace_phase, seq=seq)
        fp = self.fp_bytes.get(name)
        if fp is not None:
            attrs["fp_bytes"] = int(fp)
        with self.tracer.span(name, track="pin", **attrs):
            t0 = time.perf_counter()
            views: List[np.ndarray] = []
            off = 0
            for p in parts:
                off = -(-off // _ALIGN) * _ALIGN
                flat = p.reshape(-1).view(np.uint8)
                dst = slot.buffer[off: off + flat.nbytes]
                np.copyto(dst, flat)
                views.append(dst.view(p.dtype).reshape(p.shape))
                off += flat.nbytes
            dt = time.perf_counter() - t0
            with self._pin_lock:
                self._pin_seconds += dt
        return tuple(views) if isinstance(src, (tuple, list)) else views[0]

    def _submit_pin(self, slot: PinSlot, name: str) -> None:
        """Assign the next per-module seq and start the staging copy.
        Caller must hold the ring lock."""
        seq = self._seq.get(name, -1) + 1
        self._seq[name] = seq
        slot.name = name
        slot.seq = seq
        slot.ready = self._pinner.submit(self._do_pin, slot, name, seq)

    def seq_of(self, name: str) -> Optional[int]:
        """Pin sequence number of the currently staged copy of ``name``
        (None when nothing is staged) — the link attribute the engine
        stamps on the transfer/device spans this pin feeds."""
        ring = self.rings[self.groups[name]]
        with ring.lock:
            slot = ring.slot_for(name)
            return None if slot is None else slot.seq

    @property
    def pin_seconds(self) -> float:
        with self._pin_lock:
            return self._pin_seconds

    def reset_pin_seconds(self) -> None:
        with self._pin_lock:
            self._pin_seconds = 0.0

    # ------------------------------------------------------------------
    def prefetch(self, name: Optional[str]) -> bool:
        """Begin staging ``name`` if a slot is free.  Non-blocking.

        Returns True if staging was started (or already staged/running).
        """
        if name is None:
            return False
        ring = self.rings[self.groups[name]]
        with ring.lock:
            if ring.slot_for(name) is not None:
                return True
            slot = ring.free_slot()
            if slot is None:
                return False          # ring full: caller retries after release
            self._submit_pin(slot, name)
            self.tracer.event("pin_start", track="pin", module=name,
                              phase=self.trace_phase)
            return True

    def acquire(self, name: str) -> Entry:
        """Return the staged weights for ``name``.

        Pins synchronously if the prefetch never happened (the non-async
        ablation path).  If the ring is clogged by prefetched-but-unconsumed
        entries (out-of-order access), the least-relevant staged slot is
        evicted — ``acquire`` always makes progress unless both slots are
        simultaneously *in use*, which the engine's prompt ``release`` rules
        out.

        The whole call is one ``pin`` span on the ``wait`` track (the
        caller's wait for its staged weights), with ``miss=True`` when
        the prefetch never happened and the pin ran synchronously.
        """
        with self.tracer.span("pin", track="wait", module=name,
                              phase=self.trace_phase) as sp:
            ready, missed = self._take(name)
            if missed:
                sp.set(miss=True)
            return ready.result()

    def acquire_ahead(self, name: str) -> Entry:
        """:meth:`acquire` for a caller that is not the serving thread
        (the engine's transfer task, fetching a shard ahead of its
        linear): the same slot and weights, and no ``wait`` span."""
        return self._take(name)[0].result()

    def _take(self, name: str) -> Tuple[Future, bool]:
        """Mark ``name``'s slot in use, pinning it now if it is not
        staged; its staging future, and whether the pin was a miss."""
        ring = self.rings[self.groups[name]]
        with ring.lock:
            slot = ring.slot_for(name)
            missed = slot is None
            if missed:
                slot = self._claim_slot(ring, name)
                self._submit_pin(slot, name)
                self.tracer.event("pin_sync", track="pin", module=name,
                                  phase=self.trace_phase)
            slot.in_use = True
            return slot.ready, missed

    def _claim_slot(self, ring: GroupRing, name: str) -> PinSlot:
        """A slot to pin ``name`` into now: a free one, else a staged,
        not-in-use one, evicted.  Caller must hold the ring lock."""
        slot = ring.free_slot()
        if slot is not None:
            return slot
        deadline = time.monotonic() + 30.0
        while slot is None:
            for s in ring.slots:
                if not s.in_use and s.name != name:
                    slot = s
                    break
            if slot is None:
                if not ring.lock.wait(timeout=0.5) and \
                        time.monotonic() > deadline:
                    raise RuntimeError(
                        f"pin ring wedged acquiring {name!r}: "
                        f"both slots in use")
        if slot.ready is not None:
            slot.ready.result()   # drain in-flight pin first
            self.tracer.event("evict", track="pin", module=slot.name,
                              phase=self.trace_phase)
        return slot

    def release(self, name: str) -> None:
        """Mark ``name``'s slot reusable (its transfer has consumed it)."""
        ring = self.rings[self.groups[name]]
        with ring.lock:
            slot = ring.slot_for(name)
            if slot is not None:
                slot.name = None
                slot.ready = None
                slot.in_use = False
                ring.lock.notify_all()

    # ------------------------------------------------------------------
    def pinned_overhead_bytes(self) -> int:
        """Total staging memory — paper bound: <= 2 slots per group."""
        return sum(2 * r.slot_bytes for r in self.rings.values())

    def shutdown(self) -> None:
        self._pinner.shutdown(wait=True)


def plan_prefetch_order(plan: Sequence[str], groups: Dict[str, str]
                        ) -> Dict[str, Optional[str]]:
    """next-same-group module for each module, wrapping to the next step.

    Implements Fig. 6: "the preceding heterogeneous module prepares the
    pinned weights for the subsequent parameters ... if it is the last
    module within a layer, it proceeds to the first parameter in the
    following layer" (and the last module of the step wraps to the first of
    the next step).
    """
    nxt: Dict[str, Optional[str]] = {}
    by_group: Dict[str, List[str]] = {}
    for name in plan:
        by_group.setdefault(groups[name], []).append(name)
    for g, names in by_group.items():
        for i, name in enumerate(names):
            nxt[name] = names[(i + 1) % len(names)] if len(names) > 1 else None
    return nxt
