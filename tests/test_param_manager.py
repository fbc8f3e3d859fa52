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


@pytest.mark.parametrize("method", ["acquire", "acquire_ahead"])
def test_acquire_ahead_records_no_wait(method):
    """The engine's look-ahead task acquires on the transfer thread: its
    acquire leaves the serving thread's ``wait`` track alone, and the pin
    it ran, a miss here, still shows on the pin track."""
    w = _mk(["a"])
    tr = Tracer()
    mgr = AsyncParamManager(w, {"a": "g"}, tracer=tr)
    np.testing.assert_array_equal(getattr(mgr, method)("a"), w["a"])
    mgr.release("a")
    mgr.shutdown()
    waits = [s.attrs for s in tr.spans(track="wait")]
    assert waits == ([{"module": "a", "phase": None, "miss": True}]
                     if method == "acquire" else [])
    assert [s.name for s in tr.spans(track="pin")] == ["a"]
    assert [e.name for e in tr.events_list(track="pin")] == ["pin_sync"]


def _own_device_memory(eng):
    """Make a CPU engine behave as on a device with its own memory: each
    DMA reads a private copy of the slot, and the transfer task releases
    the slot once the DMA has completed."""
    eng._release_on_dma = True
    transfer = eng._transfer

    def own_copy(buf, *a):
        copy = (tuple(np.array(p) for p in buf) if isinstance(buf, tuple)
                else np.array(buf))
        return transfer(copy, *a)

    eng._transfer = own_copy


@pytest.mark.parametrize("wstream", ["fp", "q8"])
@pytest.mark.parametrize("device_memory", ["zero_copy", "own"])
def test_slot_release_never_corrupts_a_shard(device_memory, wstream):
    """On the CPU backend ``device_put`` zero-copies, so the engine keeps
    a slot until the device matmul has read it, the slot of a shard sent
    ahead included.  On a device with its own memory (a TPU), emulated
    here by handing each DMA a private copy of the slot, the transfer
    task releases the slot as soon as its DMA has completed, so the pin
    thread can re-stage it while the serving thread computes.  Either
    way, scribbling over every slot the moment it is released (as a
    re-stage would) changes no output of three look-ahead steps."""
    import threading

    import jax.numpy as jnp

    from repro.core import HeteGenEngine, ModulePlan

    rng = np.random.default_rng(1)
    names = [f"m{i}" for i in range(5)]
    W = {n: rng.standard_normal((96, 256)).astype(np.float32) for n in names}
    eng = HeteGenEngine(W, [ModulePlan(n, "g", "hetegen", 0.5)
                            for n in names], wstream=wstream)
    assert eng.device.platform == "cpu" and not eng._release_on_dma
    if device_memory == "own":
        _own_device_memory(eng)
    mgr = eng.manager
    release = mgr.release
    threads = []

    def scribble(name):
        threads.append(threading.current_thread().name)
        ring = mgr.rings[mgr.groups[name]]
        with ring.lock:                 # before any re-stage can start
            slot = ring.slot_for(name)
            release(name)
            slot.buffer.fill(0xFF)      # NaN in every float lane

    mgr.release = scribble
    eng.warm_prefetch()
    x = jnp.asarray(rng.standard_normal((3, 1, 96)).astype(np.float32))
    try:
        steps = []
        for _ in range(3):
            steps.append({n: np.asarray(eng.linear(x, n)) for n in names})
            eng.warm_prefetch()
        assert eng.stats.transfers_ahead >= 2 * len(names)
        assert len(threads) >= 3 * len(names)
        releasers = {t.split("_")[0] for t in threads}
        assert releasers == ({"transfer"} if device_memory == "own"
                             else {"MainThread"})
        for n in names:
            assert np.isfinite(steps[0][n]).all()
            for got in steps[1:]:
                np.testing.assert_array_equal(got[n], steps[0][n])
    finally:
        eng.close()


@pytest.mark.parametrize("device_memory", ["zero_copy", "own"])
def test_lookahead_under_fast_thread_switching(device_memory):
    """Three engines, each served by its own thread (12 threads with the
    engines' pin, transfer and GEMM threads), switch threads every 10 us
    over twelve steps with calls out of the predicted order among them:
    every step gives its first pass's bits, and every thread finishes."""
    import sys
    import threading

    import jax.numpy as jnp

    from repro.core import HeteGenEngine, ModulePlan

    names = [f"m{i}" for i in range(4)]
    errors, done = [], []

    def serve(k):
        rng = np.random.default_rng(10 + k)
        W = {n: rng.standard_normal((64, 256)).astype(np.float32)
             for n in names}
        eng = HeteGenEngine(W, [ModulePlan(n, f"g{i % 2}", "hetegen", 0.5)
                                for i, n in enumerate(names)])
        if device_memory == "own":
            _own_device_memory(eng)
        x = jnp.asarray(rng.standard_normal((2, 1, 64)).astype(np.float32))
        try:
            eng.warm_prefetch()
            ref = {n: np.asarray(eng.linear(x, n)) for n in names}
            for step in range(12):
                eng.warm_prefetch()
                order = list(names)
                if step % 3 == 2:       # a miss: two linears swapped
                    order[1], order[2] = order[2], order[1]
                for n in order:
                    np.testing.assert_array_equal(
                        np.asarray(eng.linear(x, n)), ref[n])
            assert eng.stats.transfers_ahead > 12 * len(names) // 2
            done.append(k)
        except Exception as e:           # reported on the main thread
            errors.append(e)
        finally:
            eng.close()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=serve, args=(k,))
                   for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert sorted(done) == [0, 1, 2]
