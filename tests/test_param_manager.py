"""Async parameter manager: staging order, ring bounds, no deadlock."""
import numpy as np
import pytest

from repro.core.param_manager import AsyncParamManager, plan_prefetch_order
from repro.telemetry.tracer import Tracer


def _mk(names, shape=(64, 64)):
    rng = np.random.default_rng(0)
    return {n: rng.standard_normal(shape).astype(np.float32) for n in names}


def test_acquire_returns_exact_weights():
    w = _mk(["a", "b", "c", "d"])
    mgr = AsyncParamManager(w, {n: "g" for n in w})
    for n in ["a", "b", "c", "d", "a", "c"]:   # includes out-of-order reuse
        got = mgr.acquire(n)
        np.testing.assert_array_equal(got, w[n])
        mgr.release(n)
    mgr.shutdown()


def test_prefetch_overlap_order():
    w = _mk([f"m{i}" for i in range(6)])
    groups = {n: "g" for n in w}
    tr = Tracer()
    mgr = AsyncParamManager(w, groups, tracer=tr)
    order = list(w)
    nxt = plan_prefetch_order(order, groups)
    mgr.prefetch(order[0])
    for n in order:
        if nxt[n]:
            mgr.prefetch(nxt[n])
        got = mgr.acquire(n)
        np.testing.assert_array_equal(got, w[n])
        mgr.release(n)
    mgr.shutdown()
    evs = tr.events_list(track="pin")
    # every module's pin started ahead of its acquire (plus the wrap
    # prefetch of the next step's first module); no acquire missed
    starts = [e.attrs["module"] for e in evs if e.name == "pin_start"]
    assert starts == order + [order[0]]
    assert not [e for e in evs if e.name != "pin_start"]
    waits = tr.spans(track="wait")
    assert [s.attrs["module"] for s in waits] == order
    assert not any(s.attrs.get("miss") for s in waits)
    assert not hasattr(mgr, "events")


def test_ring_bound_two_slots_per_group():
    w = _mk([f"m{i}" for i in range(8)])
    mgr = AsyncParamManager(w, {n: ("attn" if i % 2 else "mlp")
                                for i, n in enumerate(w)})
    per_slot = 64 * 64 * 4
    assert mgr.pinned_overhead_bytes() == 2 * 2 * per_slot
    mgr.shutdown()


def test_groups_isolated():
    w = _mk(["a1", "a2", "m1", "m2"], shape=(32, 32))
    mgr = AsyncParamManager(w, {"a1": "attn", "a2": "attn",
                                "m1": "mlp", "m2": "mlp"})
    mgr.prefetch("a1"); mgr.prefetch("m1")
    np.testing.assert_array_equal(mgr.acquire("a1"), w["a1"])
    np.testing.assert_array_equal(mgr.acquire("m1"), w["m1"])
    mgr.release("a1"); mgr.release("m1")
    mgr.shutdown()


def test_eviction_unclogs_ring():
    """Prefetched-but-unconsumed entries must not deadlock acquire."""
    w = _mk(["a", "b", "c"])
    tr = Tracer()
    mgr = AsyncParamManager(w, {n: "g" for n in w}, tracer=tr)
    mgr.prefetch("a"); mgr.prefetch("b")     # ring full with a, b
    got = mgr.acquire("c")                   # must evict, not hang
    np.testing.assert_array_equal(got, w["c"])
    mgr.release("c")
    mgr.shutdown()
    # the miss is visible: an eviction, a synchronous pin, a missed wait
    evs = [(e.name, e.attrs["module"]) for e in tr.events_list(track="pin")]
    assert evs[:2] == [("pin_start", "a"), ("pin_start", "b")]
    assert evs[2][0] == "evict" and evs[3] == ("pin_sync", "c")
    (wait,) = tr.spans(track="wait")
    assert wait.attrs == {"module": "c", "phase": None, "miss": True}


def test_wrap_around_prefetch_order():
    groups = {"x0": "g", "x1": "g", "x2": "g"}
    nxt = plan_prefetch_order(["x0", "x1", "x2"], groups)
    assert nxt == {"x0": "x1", "x1": "x2", "x2": "x0"}
