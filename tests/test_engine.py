"""HeteGenEngine: split-linear exactness, stream stats, placement modes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HeteGenEngine, ModulePlan


def _engine(rng, modes, n_in=96, n_out=256):
    names = [f"m{i}" for i in range(len(modes))]
    W = {n: rng.standard_normal((n_in, n_out)).astype(np.float32)
         for n in names}
    plan = [ModulePlan(n, "g", mode, alpha)
            for n, (mode, alpha) in zip(names, modes)]
    return W, HeteGenEngine(W, plan)


MODES = [("resident", 1.0), ("hetegen", 0.5), ("hetegen", 0.25),
         ("stream", 1.0), ("host", 0.0)]
# activation shapes (K = 96): decode batches (B, 1, K), a prefill
# (1, S, K), and a 2-D (M, K) -- the case each mode's id names alone
SHAPES = [(4, 96), (1, 1, 96), (4, 1, 96), (16, 1, 96), (1, 40, 96)]


def _held(W, layout):
    """The weights as the engine is given them: ``(in, out)`` arrays, or
    (as ``HeteGenBackend`` holds them) the ``(in, out)`` views of
    ``(out, in)`` arrays."""
    if layout == "in_out":
        return W
    return {n: np.ascontiguousarray(w.T).T for n, w in W.items()}


def _exact_cases():
    for mode, alpha in MODES:
        for shape in SHAPES:
            for wstream in ("fp", "q8"):
                if wstream == "q8" and mode not in ("hetegen", "stream"):
                    continue                  # no streamed shard to quantize
                for layout in ("in_out", "out_in"):
                    if layout == "out_in" and shape not in SHAPES[::3]:
                        continue
                    ident = f"{mode}-{alpha}"
                    if (shape, wstream, layout) != (SHAPES[0], "fp",
                                                     "in_out"):
                        ident += f"-{wstream}-" + "x".join(map(str, shape))
                    if layout == "out_in":
                        ident += "-out_in"
                    yield pytest.param(mode, alpha, shape, wstream, layout,
                                       id=ident)


@pytest.mark.parametrize("mode,alpha,shape,wstream,layout",
                         list(_exact_cases()))
def test_linear_exact_each_mode(rng, mode, alpha, shape, wstream, layout):
    names = ["m0", "m1", "m2"]
    W = {n: rng.standard_normal((96, 256)).astype(np.float32)
         for n in names}
    eng = HeteGenEngine(_held(W, layout),
                        [ModulePlan(n, "g", mode, alpha) for n in names],
                        wstream=wstream)
    eng.warm_prefetch()
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    for n in W:
        y = np.asarray(eng.linear(x, n))
        w = W[n].copy()
        cols = eng._dev_cols.get(n, 0)
        if wstream == "q8" and cols:
            # the streamed columns run on their int8 + scale copy
            q, scale = eng.manager.weights[n]
            w[:, :cols] = q.astype(np.float32) * scale
        ref = np.asarray(x) @ w
        assert y.shape == shape[:-1] + (256,)
        np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5)
    eng.close()


@pytest.mark.parametrize("layout", ["in_out", "out_in"])
def test_host_share_layout(rng, layout):
    """Every fp share is held (out, in) and C-contiguous.  Weights that
    are views of (out, in) arrays are sliced, with nothing copied; weights
    held (in, out) are copied into that layout, the bytes of the weight
    and no more, except a whole weight left to the host, which stays a
    view either way."""
    modes = [("hetegen", 0.5), ("host", 0.0), ("stream", 1.0),
             ("hetegen", 0.25), ("hetegen", 0.1), ("resident", 1.0)]
    names = [f"m{i}" for i in range(len(modes))]
    W = _held({n: rng.standard_normal((96, 512)).astype(np.float32)
               for n in names}, layout)
    eng = HeteGenEngine(W, [ModulePlan(n, "g", mode, alpha)
                            for n, (mode, alpha) in zip(names, modes)])
    split = {"m0": 256, "m2": 512, "m3": 128}
    try:
        for n, cols in split.items():
            assert eng._dev_cols[n] == cols
            shard = eng.manager.weights[n]
            assert shard.shape == (cols, 96) and shard.flags.c_contiguous
            np.testing.assert_array_equal(shard, W[n][:, :cols].T)
            if cols < 512:
                share = eng._host_part[n]
                assert share.shape == (512 - cols, 96)
                assert share.flags.c_contiguous
                np.testing.assert_array_equal(share, W[n][:, cols:].T)
        # alpha 0.1 of 512 columns rounds to no device tile
        for n in ("m1", "m4"):
            assert eng._dev_cols[n] == 0
            assert eng._host_part[n].shape == (512, 96)
            assert np.shares_memory(eng._host_part[n], W[n])
        np.testing.assert_array_equal(np.asarray(eng._resident["m5"]),
                                      W["m5"].T)
        assert eng.host_bytes_copied == (
            sum(W[n].nbytes for n in split) if layout == "in_out" else 0)
    finally:
        eng.close()


def test_bias_applied(rng):
    W = {"m0": rng.standard_normal((64, 128)).astype(np.float32)}
    b = {"m0": rng.standard_normal((128,)).astype(np.float32)}
    eng = HeteGenEngine(W, [ModulePlan("m0", "g", "hetegen", 0.5)], biases=b)
    x = jnp.asarray(rng.standard_normal((2, 64)).astype(np.float32))
    y = np.asarray(eng.linear(x, "m0"))
    np.testing.assert_allclose(y, np.asarray(x) @ W["m0"] + b["m0"],
                               rtol=1e-5, atol=1e-5)
    eng.close()


def test_alpha_quantization_to_tiles(rng):
    W, eng = _engine(rng, [("hetegen", 0.3)], n_out=512)
    # 0.3 * 512 = 153.6 -> nearest 128-tile = 128 cols on device
    assert eng._dev_cols["m0"] == 128
    eng.close()


def test_stream_stats_populated(rng):
    W, eng = _engine(rng, [("hetegen", 0.5)] * 4)
    eng.warm_prefetch()
    x = jnp.asarray(rng.standard_normal((2, 96)).astype(np.float32))
    for n in W:
        eng.linear(x, n)
    st = eng.finish_stats()
    assert st.cpu > 0 and st.dev > 0 and st.wall > 0
    assert st.pin > 0 and st.trans > 0
    eng.close()


def test_resident_bytes_accounting(rng):
    W, eng = _engine(rng, [("resident", 1.0), ("hetegen", 0.5)])
    assert eng.device_resident_bytes() == 96 * 256 * 4
    assert eng.pinned_overhead_bytes() > 0
    eng.close()


# ---------------------------------------------------------------------------
# look-ahead transfer: each split linear's DMA is issued one linear ahead
# ---------------------------------------------------------------------------

# two size groups, a resident and a host-only linear among the split ones:
# the look-ahead skips every linear without a device share
def _lookahead_engine(rng, alpha, wstream="fp", tracer=None):
    from repro.telemetry.tracer import NULL_TRACER

    plan = [ModulePlan("q", "attn", "hetegen", alpha),
            ModulePlan("o", "attn", "hetegen", alpha),
            ModulePlan("r", "attn", "resident", 1.0),
            ModulePlan("up", "mlp", "hetegen", alpha),
            ModulePlan("h", "mlp", "host", 0.0),
            ModulePlan("down", "mlp", "hetegen", alpha)]
    W = {p.name: rng.standard_normal((96, 256)).astype(np.float32)
         for p in plan}
    eng = HeteGenEngine(W, plan, wstream=wstream,
                        tracer=tracer or NULL_TRACER, trace_phase="decode")
    eng.warm_prefetch()
    return W, eng


def _step(eng, x):
    return {n: np.asarray(eng.linear(x, n)) for n in eng.order}


def _split(eng):
    return [n for n in eng.order if eng._dev_cols.get(n, 0) > 0]


@pytest.mark.parametrize("wstream", ["fp", "q8"])
@pytest.mark.parametrize("alpha", [0.0, 0.5, 1.0])
def test_lookahead_equals_on_demand_bit_for_bit(rng, alpha, wstream):
    """The engine's first pass fetches every shard on demand, the second
    (after the step boundary) one linear ahead: the outputs are the same
    bits, and the right ones."""
    W, eng = _lookahead_engine(rng, alpha, wstream)
    x = jnp.asarray(rng.standard_normal((4, 1, 96)).astype(np.float32))
    try:
        first = _step(eng, x)
        eng.warm_prefetch()
        second = _step(eng, x)
        n = len(_split(eng))
        assert n == (0 if alpha == 0.0 else 4)
        assert (eng.stats.transfers_on_demand,
                eng.stats.transfers_ahead) == (n, n + 1 if n else 0)
        for name in eng.order:
            np.testing.assert_array_equal(second[name], first[name])
            w = W[name].copy()
            cols = eng._dev_cols.get(name, 0)
            if wstream == "q8" and cols:
                q, scale = eng.manager.weights[name]
                w[:, :cols] = q.astype(np.float32) * scale
            np.testing.assert_allclose(first[name], np.asarray(x) @ w,
                                       rtol=1e-5, atol=1e-5)
    finally:
        eng.close()


@pytest.mark.parametrize("boundary", ["warm_prefetch", "repeat"])
def test_second_step_transfers_are_ahead(rng, boundary):
    """Once the first pass's order is known every transfer of the second
    step is issued ahead, the wrap to the next step's first linear
    included.  The backend marks the step boundary (``warm_prefetch``);
    without it the first repeated call closes the order, and only that
    call is fetched on demand."""
    from repro.telemetry.tracer import Tracer

    tr = Tracer()
    _, eng = _lookahead_engine(rng, 0.5, tracer=tr)
    x = jnp.asarray(rng.standard_normal((2, 1, 96)).astype(np.float32))
    split = _split(eng)
    try:
        _step(eng, x)
        mark = tr.mark()
        if boundary == "warm_prefetch":
            eng.warm_prefetch()
        _step(eng, x)
    finally:
        eng.close()
    second = [(s.attrs["module"], s.attrs["ahead"])
              for s in tr.spans(since=mark, track="transfer")]
    on_demand = boundary == "repeat"
    assert second == [(split[0], not on_demand)] + \
        [(n, True) for n in split[1:]] + [(split[0], True)]
    assert eng.stats.transfers_ahead == len(split) + (0 if on_demand else 1)
    assert eng.stats.transfers_on_demand == len(split) + on_demand


def test_out_of_order_call_is_a_miss(rng):
    """A call other than the predicted next is fetched on demand and gives
    the right result; the shard in flight stays for its own call."""
    _, eng = _lookahead_engine(rng, 0.5)
    x = jnp.asarray(rng.standard_normal((2, 1, 96)).astype(np.float32))
    q, o, up, down = _split(eng)
    try:
        ref = _step(eng, x)
        eng.warm_prefetch()
        assert eng._ahead[0] == q
        ahead, on_demand = (eng.stats.transfers_ahead,
                            eng.stats.transfers_on_demand)
        np.testing.assert_array_equal(np.asarray(eng.linear(x, up)), ref[up])
        assert eng.stats.transfers_on_demand == on_demand + 1
        assert eng.stats.transfers_ahead == ahead
        assert eng._ahead[0] == q          # still in flight, for q
        got = _step(eng, x)                # q hits, and the chain resumes
        for n in eng.order:
            np.testing.assert_array_equal(got[n], ref[n])
        # o, up, down and the wrap to q were issued ahead
        assert eng.stats.transfers_on_demand == on_demand + 1
        assert eng.stats.transfers_ahead == ahead + 4
    finally:
        eng.close()


def test_at_most_one_shard_in_flight(rng):
    """Over steps with a miss among them, no more than one DMA issued
    ahead is ever waiting for its linear."""
    _, eng = _lookahead_engine(rng, 0.5)
    x = jnp.asarray(rng.standard_normal((2, 1, 96)).astype(np.float32))
    pending = []
    submit = eng._trans_pool.submit

    def spy(fn, name, buf):
        if buf is None:
            pending.append(name)
        return submit(fn, name, buf)

    eng._trans_pool.submit = spy
    calls = list(eng.order) * 2 + ["up"] + list(eng.order) * 2
    try:
        for i, n in enumerate(calls):
            if i and calls[i - 1] == eng.order[-1]:
                eng.warm_prefetch()
                assert len(pending) <= 1
            if n in pending:
                pending.remove(n)
            eng.linear(x, n)
            assert len(pending) <= 1, (i, n, pending)
        assert eng.stats.transfers_ahead > 3 * len(_split(eng))
    finally:
        eng.close()


def test_close_drops_the_in_flight_shard(rng):
    _, eng = _lookahead_engine(rng, 0.5)
    x = jnp.asarray(rng.standard_normal((2, 1, 96)).astype(np.float32))
    _step(eng, x)
    eng.warm_prefetch()
    assert eng._ahead is not None
    eng.close()
    assert eng._ahead is None


def test_retune_drops_the_in_flight_shard():
    """A backend that rebuilds a phase's engine, or closes, frees the
    shard the old engine had in flight, and sums the engines' counts."""
    import dataclasses

    import jax

    from repro.configs import get_config, reduced
    from repro.core.hw import PAPER_A10
    from repro.models import model as M
    from repro.serving.backends import HeteGenBackend

    cfg = dataclasses.replace(
        reduced(get_config("opt-125m"), layers=1), name="opt-split",
        d_model=256, n_heads=4, head_dim=64, d_ff=512)
    be = HeteGenBackend(cfg, M.init_params(cfg, jax.random.PRNGKey(1)),
                        hw=PAPER_A10, budget_bytes=0, batch=2,
                        alpha_override=0.5)

    def step(eng):
        for n in eng.order:
            k = be._host_weights[n].shape[0]
            eng.linear(jnp.ones((2, 1, k), jnp.float32), n)
        be.prefetch_next_step()

    old = be.engine
    step(old)
    assert old._ahead is not None
    issued = old.stats.transfers_ahead
    be.retune(3)
    assert old._ahead is None and be.engine is not old
    new = be.engine
    step(new)
    assert new._ahead is not None
    # the backend's counts keep the rebuilt engine's
    assert be.finish_stats().transfers_ahead == \
        issued + new.stats.transfers_ahead > issued > 0
    be.close()
    assert new._ahead is None
