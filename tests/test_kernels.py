"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret
mode on CPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


# ---- ring_window -----------------------------------------------------------

@pytest.mark.parametrize("C,cap,m", [(1, 64, 16), (5, 256, 64),
                                     (10, 1024, 1024), (3, 128, 128)])
def test_ring_window_shapes(C, cap, m, rng):
    store = jnp.asarray(rng.integers(0, 10**6, (C, cap)), jnp.int32)
    front = jnp.asarray(rng.integers(0, cap, C), jnp.int32)
    counts = jnp.asarray(rng.integers(0, m + 1, C), jnp.int32)
    got = ops.ring_window(store, front, counts, m=m)
    want = ref.ring_window_ref(store, front, counts, m)
    np.testing.assert_array_equal(got, want)


def test_ring_window_wraparound(rng):
    store = jnp.arange(32, dtype=jnp.int32)[None]
    front = jnp.asarray([30], jnp.int32)
    counts = jnp.asarray([5], jnp.int32)
    got = np.asarray(ops.ring_window(store, front, counts, m=8))
    assert list(got[0][:5]) == [30, 31, 0, 1, 2]
    assert (got[0][5:] == -1).all()


# ---- bitmap_select -----------------------------------------------------------

@pytest.mark.parametrize("w", [32, 64, 256])
@pytest.mark.parametrize("k", [0, 1, 7, 100, 10**6])
def test_bitmap_select_sweep(w, k, rng):
    words = jnp.asarray(
        rng.integers(0, 2**32, w, dtype=np.uint64), jnp.uint32)
    got = ops.bitmap_select(words, k)
    want = ref.bitmap_select_ref(words, k)
    np.testing.assert_array_equal(got, want)


def test_bitmap_select_indices(rng):
    words = jnp.asarray([0b1011, 0, 1], jnp.uint32)
    idx, valid = ops.bitmap_select_indices(
        jnp.pad(words, (0, 29)), 3, max_k=4)
    assert list(np.asarray(idx)[:3]) == [0, 1, 3]
    assert list(np.asarray(valid)) == [True, True, True, False]


# ---- paged_attention ----------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,D,page,P", [
    (1, 4, 4, 128, 16, 4),     # MHA
    (2, 8, 2, 128, 16, 6),     # GQA
    (2, 8, 1, 64, 8, 8),       # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, Hq, Hkv, D, page, P, dtype, rng):
    NP = B * P + 4
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), dtype)
    kp = jnp.asarray(rng.standard_normal((NP, page, Hkv, D)), dtype)
    vp = jnp.asarray(rng.standard_normal((NP, page, Hkv, D)), dtype)
    pt = jnp.asarray(
        rng.choice(NP, (B, P), replace=False), jnp.int32)
    pt = pt.at[0, P - 1:].set(-1)
    sl = jnp.asarray(rng.integers(1, (P - 1) * page, B), jnp.int32)
    got = ops.paged_attention(q, kp, vp, pt, sl)
    want = ref.paged_attention_ref(q, kp, vp, pt, sl)
    tol = 3e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_paged_attention_word_offset_table(rng):
    """Mega-step table format: ``page_table`` holding raw arena WORD
    offsets (page id × wpp, holes −1) with ``wpp`` passed through must
    match the page-id table exactly — the division happens in the
    scalar-prefetch index map, and −1 holes stay invalid under floor
    division."""
    B, Hq, Hkv, D, page, P, wpp = 2, 8, 2, 128, 16, 6, 64
    NP = B * P + 4
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((NP, page, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NP, page, Hkv, D)), jnp.float32)
    pt = jnp.asarray(rng.choice(NP, (B, P), replace=False), jnp.int32)
    pt = pt.at[0, P - 1:].set(-1)
    sl = jnp.asarray(rng.integers(1, (P - 1) * page, B), jnp.int32)
    want = ops.paged_attention(q, kp, vp, pt, sl)
    words = jnp.where(pt >= 0, pt * wpp, -1)
    got = ops.paged_attention(q, kp, vp, words, sl, wpp=wpp)
    np.testing.assert_array_equal(got, want)


# ---- ssd_scan ------------------------------------------------------------------

@pytest.mark.parametrize("B,L,H,P,G,N,chunk", [
    (1, 64, 2, 16, 1, 32, 16),
    (2, 128, 4, 32, 2, 64, 32),
    (1, 256, 8, 64, 1, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, L, H, P, G, N, chunk, dtype, rng):
    x = jnp.asarray(rng.standard_normal((B, L, H, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, L, H)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, H), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, L, G, N)), dtype)
    c = jnp.asarray(rng.standard_normal((B, L, G, N)), dtype)
    y, hf = ops.ssd_scan(x, dt, a, b, c, chunk=chunk)
    yr, hr = ref.ssd_ref(x, dt, a, b, c)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(y, yr, atol=tol * 10, rtol=tol * 10)
    np.testing.assert_allclose(hf, hr, atol=tol * 10, rtol=tol * 10)


def test_ssd_scan_chained_states(rng):
    """Two chained half-length scans == one full scan (decode contract)."""
    B, L, H, P, G, N = 1, 64, 2, 16, 1, 32
    x = jnp.asarray(rng.standard_normal((B, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (B, L, H)), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, H), jnp.float32)
    b = jnp.asarray(rng.standard_normal((B, L, G, N)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((B, L, G, N)), jnp.float32)
    y_full, h_full = ops.ssd_scan(x, dt, a, b, c, chunk=16)
    h = L // 2
    y1, s1 = ops.ssd_scan(x[:, :h], dt[:, :h], a, b[:, :h], c[:, :h],
                          chunk=16)
    y2, s2 = ops.ssd_scan(x[:, h:], dt[:, h:], a, b[:, h:], c[:, h:],
                          h0=s1, chunk=16)
    np.testing.assert_allclose(
        np.concatenate([y1, y2], 1), y_full, atol=1e-4)
    np.testing.assert_allclose(s2, h_full, atol=1e-4)


# ---- kernel/core integration ----------------------------------------------------

def test_ring_window_matches_page_alloc(rng):
    """The kernel computes exactly what the page allocator's bulk
    dequeue gathers (rank-dense grant windows)."""
    from repro.core import HeapConfig, groups
    from repro.core import page_alloc, queues
    cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
                     min_page_bytes=16)
    st = page_alloc.init(cfg, "ring")
    sizes = jnp.asarray(rng.choice([16, 64, 256], 32), jnp.int32)
    from repro.core.heap import size_to_class_device
    cls = size_to_class_device(cfg, sizes)
    valid = cls < cfg.num_classes
    rank, counts = groups.masked_rank(cls, valid, cfg.num_classes)
    m = 32
    win = ops.ring_window(st.q.store, st.q.front % st.q.store.shape[1],
                          jnp.minimum(counts, m), m=m)
    st2, offs = page_alloc.alloc(cfg, "ring", st, sizes, valid)
    offs = np.asarray(offs)
    win = np.asarray(win)
    for i in range(32):
        if offs[i] >= 0:
            assert win[int(cls[i]), int(rank[i])] == offs[i]


def test_pallas_ring_path_equals_jnp_path(rng):
    """core/page_alloc with backend="pallas": identical grants & state
    (the fused-transaction form of the old USE_PALLAS_RING toggle)."""
    from repro.core import HeapConfig, page_alloc
    import jax.numpy as jnp
    cfg = HeapConfig(total_bytes=1 << 17, chunk_bytes=1 << 11,
                     min_page_bytes=16)
    sizes = jnp.asarray(rng.choice([16, 64, 256, 1000], 48), jnp.int32)
    mask = jnp.asarray(rng.random(48) < 0.9)

    st = page_alloc.init(cfg, "ring")
    s_ref, o_ref = page_alloc.alloc(cfg, "ring", st, sizes, mask, "jnp")
    s_ker, o_ker = page_alloc.alloc(cfg, "ring", st, sizes, mask,
                                    "pallas")
    np.testing.assert_array_equal(np.asarray(o_ref), np.asarray(o_ker))
    np.testing.assert_array_equal(np.asarray(s_ref.q.front),
                                  np.asarray(s_ker.q.front))


# ---- alloc_txn fused transactions ------------------------------------------

def test_ring_txn_pop_matches_bulk_dequeue(rng):
    """Fused pop (limit=False) == queues.ring_bulk_dequeue, including
    wraparound, masked lanes, and invalid classes."""
    from repro.core import HeapConfig, groups, queues
    C, cap, n = 5, 48, 33
    cfg = HeapConfig()
    store = jnp.asarray(rng.integers(0, 10**6, (C, cap)), jnp.int32)
    front = jnp.asarray(rng.integers(0, 100, C), jnp.int32)
    back = front + jnp.asarray(rng.integers(0, cap + 1, C), jnp.int32)
    q = queues.RingState(store=store, front=front, back=back)
    cls = jnp.asarray(rng.integers(0, C + 1, n), jnp.int32)
    mask = jnp.asarray(rng.random(n) < 0.8) & (cls < C)
    rank, _ = groups.masked_rank(cls, mask, C)

    q_ref, _, v_ref = queues.ring_bulk_dequeue(cfg, q, None, cls, rank,
                                               mask)
    v_ker, nf = ops.ring_txn_pop(store, front, back, cls, mask,
                                 limit=False)
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_ker))
    np.testing.assert_array_equal(np.asarray(q_ref.front), np.asarray(nf))


def test_ring_txn_push_matches_bulk_enqueue(rng):
    from repro.core import HeapConfig, groups, queues
    C, cap, n = 4, 32, 21
    cfg = HeapConfig()
    store = jnp.asarray(rng.integers(0, 10**6, (C, cap)), jnp.int32)
    back = jnp.asarray(rng.integers(0, 100, C), jnp.int32)
    q = queues.RingState(store=store, front=back - 3, back=back)
    cls = jnp.asarray(rng.integers(0, C + 1, n), jnp.int32)
    mask = jnp.asarray(rng.random(n) < 0.8) & (cls < C)
    vals = jnp.asarray(rng.integers(0, 10**6, n), jnp.int32)
    rank, _ = groups.masked_rank(cls, mask, C)

    q_ref, _ = queues.ring_bulk_enqueue(cfg, q, None, cls, rank, vals,
                                        mask)
    st_ker, nb = ops.ring_txn_push(store, back, cls, vals, mask)
    np.testing.assert_array_equal(np.asarray(q_ref.store),
                                  np.asarray(st_ker))
    np.testing.assert_array_equal(np.asarray(q_ref.back), np.asarray(nb))


@pytest.mark.parametrize("ppc,bw", [(32, 1), (128, 4)])
def test_chunk_txn_claim_matches_select_free_pages(ppc, bw, rng):
    from repro.core import chunk_alloc
    for take in (0, 3, 10**4):
        row = jnp.asarray(
            rng.integers(0, 2**32, bw, dtype=np.uint64), jnp.uint32)
        pi_ref, sel_ref = chunk_alloc._select_free_pages(
            row, ppc, jnp.int32(take))
        pi, nrow, nsel = ops.chunk_txn_claim(row, jnp.int32(take), ppc=ppc)
        np.testing.assert_array_equal(np.asarray(pi_ref), np.asarray(pi))
        np.testing.assert_array_equal(np.asarray(sel_ref),
                                      np.asarray(pi) >= 0)
        assert int(nsel[0]) == int(np.asarray(sel_ref).sum())
        # claimed bits set, nothing else changed
        got = np.asarray(nrow)
        exp = np.asarray(row).copy()
        for p in np.asarray(pi):
            if p >= 0:
                exp[p // 32] |= np.uint32(1) << np.uint32(p % 32)
        np.testing.assert_array_equal(exp, got)


def test_paged_attention_kernel_matches_serving_path(rng):
    """kernels/paged_attention (Pallas) == paged/kv_cache.paged_attend1
    (the GSPMD serving path) on identical paged state."""
    from repro.paged import kv_cache as KV
    B, Hq, Hkv, D, page, P = 2, 4, 2, 128, 16, 4
    NP = B * P
    kp = jnp.asarray(rng.standard_normal((NP, page, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NP, page, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    pt = (jnp.arange(B)[:, None] * P + jnp.arange(P)[None, :]).astype(
        jnp.int32)
    sl = jnp.asarray([37, 61], jnp.int32)

    kernel = ops.paged_attention(q[:, 0], kp, vp, pt, sl)
    lay = KV.KVLayer(k=KV.page_rows(kp), v=KV.page_rows(vp), k_scale=None,
                     v_scale=None)
    serving = KV.paged_attend1(lay, pt, sl, q)[:, 0]
    np.testing.assert_allclose(kernel, serving, atol=2e-5, rtol=2e-5)
