"""Per-kernel validation: interpret=True vs the pure-jnp oracles in
kernels/ref.py, swept over shapes and dtypes."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

ops.set_mode("interpret")


def _rand(rng, shape, dtype=np.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 384),
                                   (128, 256, 512)])
@pytest.mark.parametrize("act", [None, "relu", "relu2", "gelu", "silu"])
def test_matmul_shapes_acts(rng, m, k, n, act):
    x, w = _rand(rng, (m, k)), _rand(rng, (k, n))
    b = _rand(rng, (n,))
    got = ops.matmul(x, w, b, activation=act)
    want = ref.matmul(x, w, b, activation=act)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_dtypes(rng, dtype):
    x = _rand(rng, (128, 128)).astype(dtype)
    w = _rand(rng, (128, 128)).astype(dtype)
    got = ops.matmul(x, w)
    want = ref.matmul(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_gated_matmul(rng):
    x, wg, wu = _rand(rng, (128, 256)), _rand(rng, (256, 256)), \
        _rand(rng, (256, 256))
    got = ops.gated_matmul(x, wg, wu)
    # silu amplifies blocked-K accumulation differences at large |gate|
    np.testing.assert_allclose(got, ref.gated_matmul(x, wg, wu),
                               rtol=2e-3, atol=2e-3)


def test_q8_matmul(rng):
    x, w = _rand(rng, (128, 256)), _rand(rng, (256, 384))
    q, s = ops.quantize_weights(w)
    got = ops.q8_matmul(x, q, s)
    want = ref.q8_matmul(x, q, s)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    # and dequantized result approximates the fp matmul within quant error
    full = np.asarray(x) @ np.asarray(w)
    assert np.abs(np.asarray(got) - full).max() / np.abs(full).max() < 0.05


@pytest.mark.parametrize("m", [4, 200])
def test_q8_matmul_ragged_rows(rng, m):
    """Decode (M = batch) and prefill rows that no 128-row block divides:
    the kernel pads M and slices the pad rows back off."""
    x, w = _rand(rng, (m, 256)), _rand(rng, (256, 384))
    q, s = ops.quantize_weights(w)
    got = ops.q8_matmul(x, q, s)
    assert got.shape == (m, 384)
    np.testing.assert_allclose(got, ref.q8_matmul(x, q, s),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("causal,window,softcap", [
    (True, None, None), (False, None, None),
    (True, 64, None), (True, None, 20.0)])
def test_flash_attention(rng, causal, window, softcap):
    b, hq, hkv, s, d = 2, 4, 2, 256, 64
    q = _rand(rng, (b, hq, s, d))
    k = _rand(rng, (b, hkv, s, d))
    v = _rand(rng, (b, hkv, s, d))
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, block_q=64, block_kv=64)
    want = ref.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_attention_gqa_ratios(rng, hq, hkv):
    b, s, d = 1, 128, 32
    q = _rand(rng, (b, hq, s, d))
    k = _rand(rng, (b, hkv, s, d))
    v = _rand(rng, (b, hkv, s, d))
    got = ops.flash_attention(q, k, v, block_q=64, block_kv=64)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_decode_attention(rng):
    b, hq, hkv, s, d = 3, 4, 2, 256, 64
    q = _rand(rng, (b, hq, d))
    k = _rand(rng, (b, hkv, s, d))
    v = _rand(rng, (b, hkv, s, d))
    kv_len = jnp.asarray([17, 100, 256], jnp.int32)
    got = ops.decode_attention(q, k, v, kv_len, block_kv=64)
    want = ref.decode_attention(q, k, v, kv_len)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_decode_attention_matches_flash_last_row(rng):
    """decode(q_last) == flash(full)[last] when kv_len == s."""
    b, hq, hkv, s, d = 1, 4, 2, 128, 32
    q = _rand(rng, (b, hq, s, d))
    k = _rand(rng, (b, hkv, s, d))
    v = _rand(rng, (b, hkv, s, d))
    full = ref.flash_attention(q, k, v, causal=True)
    got = ops.decode_attention(q[:, :, -1], k, v,
                               jnp.asarray([s], jnp.int32), block_kv=64)
    np.testing.assert_allclose(got, full[:, :, -1], rtol=2e-5, atol=2e-5)


def _paged_pool(rng, k, v, page_size):
    """Scatter dense (B, Hkv, S, D) K/V into a permuted page pool +
    block tables (page 0 left as trash)."""
    b, hkv, s, d = k.shape
    nb = s // page_size
    n_pages = 1 + b * nb
    perm = rng.permutation(np.arange(1, n_pages))
    bt = np.zeros((b, nb), np.int32)
    kp = np.zeros((n_pages, hkv, page_size, d), np.asarray(k).dtype)
    vp = np.zeros_like(kp)
    for i in range(b):
        for j in range(nb):
            pid = int(perm[i * nb + j])
            bt[i, j] = pid
            kp[pid] = np.asarray(k[i, :, j * page_size:(j + 1) * page_size])
            vp[pid] = np.asarray(v[i, :, j * page_size:(j + 1) * page_size])
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt)


@pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 1), (4, 4)])
def test_paged_decode_attention(rng, hq, hkv):
    """Paged kernel == paged oracle == dense decode oracle: gathering K/V
    through a permuted block table changes nothing but the layout."""
    b, s, d, ps = 3, 256, 64, 16
    q = _rand(rng, (b, hq, d))
    k = _rand(rng, (b, hkv, s, d))
    v = _rand(rng, (b, hkv, s, d))
    kv_len = jnp.asarray([17, 100, 256], jnp.int32)
    kp, vp, bt = _paged_pool(rng, k, v, ps)
    want_dense = ref.decode_attention(q, k, v, kv_len)
    want = ref.paged_decode_attention(q, kp, vp, bt, kv_len)
    got = ops.paged_decode_attention(q, kp, vp, bt, kv_len)
    np.testing.assert_allclose(want, want_dense, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_decode_attention_softcap(rng):
    b, hq, hkv, s, d, ps = 2, 4, 2, 128, 32, 16
    q = _rand(rng, (b, hq, d))
    k = _rand(rng, (b, hkv, s, d))
    v = _rand(rng, (b, hkv, s, d))
    kv_len = jnp.asarray([50, 128], jnp.int32)
    kp, vp, bt = _paged_pool(rng, k, v, ps)
    got = ops.paged_decode_attention(q, kp, vp, bt, kv_len, softcap=20.0)
    want = ref.paged_decode_attention(q, kp, vp, bt, kv_len, softcap=20.0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_decode_attention_q8(rng):
    """int8 pages with per-(page, head, token) scales dequantize in the
    kernel body exactly as the q8 oracle does after gathering."""
    b, hq, hkv, s, d, ps = 2, 4, 2, 128, 32, 16
    n_pages = 1 + b * (s // ps)
    q = _rand(rng, (b, hq, d))
    k8 = jnp.asarray(rng.integers(-127, 127, (n_pages, hkv, ps, d)),
                     jnp.int8)
    v8 = jnp.asarray(rng.integers(-127, 127, (n_pages, hkv, ps, d)),
                     jnp.int8)
    ks = jnp.abs(_rand(rng, (n_pages, hkv, ps))) * 0.01
    vs = jnp.abs(_rand(rng, (n_pages, hkv, ps))) * 0.01
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(b, -1),
                     jnp.int32)
    kv_len = jnp.asarray([37, 128], jnp.int32)
    got = ops.paged_decode_attention(q, k8, v8, bt, kv_len,
                                     k_scale=ks, v_scale=vs)
    want = ref.paged_decode_attention(q, k8, v8, bt, kv_len,
                                      k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 1), (4, 4)])
def test_paged_prefill_attention(rng, hq, hkv):
    """Whole-shot paged prefill (kv_offset 0) == paged oracle == dense
    causal flash attention: block-table indirection changes layout only."""
    b, s, d, ps = 2, 128, 64, 16
    q = _rand(rng, (b, hq, s, d))
    k = _rand(rng, (b, hkv, s, d))
    v = _rand(rng, (b, hkv, s, d))
    kp, vp, bt = _paged_pool(rng, k, v, ps)
    offs = jnp.zeros((b,), jnp.int32)
    want_dense = ref.flash_attention(q, k, v, causal=True)
    want = ref.paged_prefill_attention(q, kp, vp, bt, offs)
    got = ops.paged_prefill_attention(q, kp, vp, bt, offs, block_q=32)
    np.testing.assert_allclose(want, want_dense, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_prefill_attention_chunk_offsets(rng):
    """A chunk starting mid-sequence (per-row kv_offset, non-dividing
    length) attends to every previously-written page plus its own causal
    triangle — matching rows [off, off+s) of dense full-sequence flash."""
    b, hq, hkv, t, d, ps, s = 2, 4, 2, 160, 32, 16, 37
    q_full = _rand(rng, (b, hq, t, d))
    k = _rand(rng, (b, hkv, t, d))
    v = _rand(rng, (b, hkv, t, d))
    kp, vp, bt = _paged_pool(rng, k, v, ps)
    offs = jnp.asarray([40, 103], jnp.int32)     # page-unaligned second row
    q = jnp.stack([q_full[i, :, int(o):int(o) + s]
                   for i, o in enumerate(offs)])
    full = ref.flash_attention(q_full, k, v, causal=True)
    want_rows = jnp.stack([full[i, :, int(o):int(o) + s]
                           for i, o in enumerate(offs)])
    want = ref.paged_prefill_attention(q, kp, vp, bt, offs)
    got = ops.paged_prefill_attention(q, kp, vp, bt, offs, block_q=32)
    np.testing.assert_allclose(want, want_rows, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_prefill_attention_trash_tail(rng):
    """Pages past the chunk's last position hold garbage (the trash page
    and never-written pool rows) — the causal mask must exclude them, so
    corrupting them cannot change the output."""
    b, hq, hkv, t, d, ps, s = 1, 4, 2, 128, 32, 16, 21
    q = _rand(rng, (b, hq, s, d))
    k = _rand(rng, (b, hkv, t, d))
    v = _rand(rng, (b, hkv, t, d))
    kp, vp, bt = _paged_pool(rng, k, v, ps)
    offs = jnp.asarray([30], jnp.int32)
    base = ops.paged_prefill_attention(q, kp, vp, bt, offs, block_q=32)
    # poison everything past kv_len = off + s
    end_page = -(-int(offs[0] + s) // ps)
    poison_ids = np.asarray(bt)[0, end_page:]
    kp2 = np.array(kp)
    vp2 = np.array(vp)
    kp2[poison_ids] = np.nan
    vp2[poison_ids] = np.nan
    got = ops.paged_prefill_attention(q, jnp.asarray(kp2), jnp.asarray(vp2),
                                      bt, offs, block_q=32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


@pytest.mark.parametrize("window,softcap", [(24, None), (None, 20.0)])
def test_paged_prefill_attention_window_softcap(rng, window, softcap):
    b, hq, hkv, t, d, ps, s = 2, 4, 2, 128, 32, 16, 32
    q = _rand(rng, (b, hq, s, d))
    k = _rand(rng, (b, hkv, t, d))
    v = _rand(rng, (b, hkv, t, d))
    kp, vp, bt = _paged_pool(rng, k, v, ps)
    offs = jnp.asarray([0, 77], jnp.int32)
    got = ops.paged_prefill_attention(q, kp, vp, bt, offs, window=window,
                                      softcap=softcap, block_q=32)
    want = ref.paged_prefill_attention(q, kp, vp, bt, offs, window=window,
                                       softcap=softcap)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_prefill_attention_q8(rng):
    """int8 pages with per-(page, head, token) scales dequantize in the
    kernel body exactly as the q8 oracle does after gathering."""
    b, hq, hkv, t, d, ps, s = 2, 4, 2, 128, 32, 16, 19
    n_pages = 1 + b * (t // ps)
    q = _rand(rng, (b, hq, s, d))
    k8 = jnp.asarray(rng.integers(-127, 127, (n_pages, hkv, ps, d)),
                     jnp.int8)
    v8 = jnp.asarray(rng.integers(-127, 127, (n_pages, hkv, ps, d)),
                     jnp.int8)
    ks = jnp.abs(_rand(rng, (n_pages, hkv, ps))) * 0.01
    vs = jnp.abs(_rand(rng, (n_pages, hkv, ps))) * 0.01
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(b, -1),
                     jnp.int32)
    offs = jnp.asarray([16, 55], jnp.int32)
    got = ops.paged_prefill_attention(q, k8, v8, bt, offs,
                                      k_scale=ks, v_scale=vs, block_q=32)
    want = ref.paged_prefill_attention(q, k8, v8, bt, offs,
                                       k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", [(4, 128), (2, 33, 128), (3, 5, 7, 256)])
@pytest.mark.parametrize("plus_one", [False, True])
def test_rmsnorm(rng, shape, plus_one):
    x = _rand(rng, shape)
    s = _rand(rng, (shape[-1],))
    got = ops.rmsnorm(x, s, plus_one=plus_one)
    want = ref.rmsnorm(x, s, plus_one=plus_one)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_ssd_chunk_vs_oracle_and_recurrent(rng):
    from repro.models.ssm import ssd_recurrent
    b, l, h, p, n, ch = 2, 64, 3, 16, 8, 16
    x = _rand(rng, (b, l, h, p))
    dt = jnp.abs(_rand(rng, (b, l, h))) * 0.5
    a = -jnp.abs(_rand(rng, (h,))) * 0.5
    bm = _rand(rng, (b, l, h, n))
    cm = _rand(rng, (b, l, h, n))
    y_k, sc_k, cum_k = ops.ssd_chunk(x, dt, a, bm, cm, chunk=ch)
    y_r, sc_r, cum_r = ref.ssd_chunk(x, dt, a, bm, cm, chunk=ch)
    np.testing.assert_allclose(y_k, y_r, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(sc_k, sc_r, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(cum_k, cum_r, rtol=2e-5, atol=2e-5)
