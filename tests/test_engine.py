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
