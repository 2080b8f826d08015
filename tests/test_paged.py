"""Paged KV cache unit tests: append/prefill writes, paged attention vs
dense flash, int8 quantization error bounds, windowed masking, and the
model's carried heap against a per-layer reference."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.models.model import build_model
from repro.models import transformer as TF
from repro.models.layers import flash_attention
from repro.paged import kv_cache as KV


def _layer(rng, NP=16, page=8, Hkv=2, D=32, dtype=jnp.float32, L=1):
    """An empty stacked heap of ``L`` layers, pages (page, *kv_row)."""
    return KV.init_paged_kv(L, NP, 1, 1, Hkv, D, dtype, page).layers


def _attend(lay, *args, **kw):
    return KV.paged_attend1(KV.layer_of(lay, 0), *args, **kw)


def _pt(B, P):
    return (jnp.arange(B)[:, None] * P + jnp.arange(P)[None, :]).astype(
        jnp.int32)


def test_prefill_then_append_then_attend(rng):
    B, S, Hq, Hkv, D, page = 2, 24, 4, 2, 32, 8
    P = 4
    lay = _layer(rng, NP=B * P, page=page, Hkv=Hkv, D=D)
    pt = _pt(B, P)
    k = jnp.asarray(rng.standard_normal((B, S + 1, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S + 1, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)

    lay = KV.prefill_write_at(lay, 0, pt, k[:, :S], v[:, :S])
    lay = KV.append1_at(lay, 0, pt, jnp.full(B, S, jnp.int32),
                        k[:, S:], v[:, S:])
    got = _attend(lay, pt, jnp.full(B, S + 1, jnp.int32), q,
                  page_block=2)
    want = flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("dtype", (jnp.float32, jnp.int8))
def test_wide_tokens_stored_in_rows_of_lanes(rng, dtype):
    """A token of more than 128 values (2 heads of 128) is stored as
    rows of 128 lanes, (page, 2, 128); written by a prefill and an
    append, it reads back through paged attention as dense attention
    over the same K/V does."""
    B, S, Hq, Hkv, D, page, P = 2, 20, 4, 2, 128, 8, 3
    lay = _layer(rng, NP=B * P, page=page, Hkv=Hkv, D=D, dtype=dtype)
    assert lay.k.shape == (1, B * P, page, 2, 128)
    pt = _pt(B, P)
    k = jnp.asarray(rng.standard_normal((B, S + 1, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S + 1, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)

    lay = KV.prefill_write_at(lay, 0, pt, k[:, :S], v[:, :S])
    lay = KV.append1_at(lay, 0, pt, jnp.full(B, S, jnp.int32),
                        k[:, S:], v[:, S:])
    got = _attend(lay, pt, jnp.full(B, S + 1, jnp.int32), q)
    want = flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_paged_attend_respects_kv_len(rng):
    B, Hq, Hkv, D, page, P = 1, 2, 1, 16, 4, 4
    lay = _layer(rng, NP=P, page=page, Hkv=Hkv, D=D)
    pt = _pt(B, P)
    T = P * page
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    lay = KV.prefill_write_at(lay, 0, pt, k, v)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    for kv_len in (1, 5, 12):
        got = _attend(lay, pt, jnp.asarray([kv_len]), q)
        want = flash_attention(q, k[:, :kv_len], v[:, :kv_len],
                               causal=False)
        np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_paged_attend_window(rng):
    B, Hq, Hkv, D, page, P = 1, 2, 1, 16, 4, 8
    lay = _layer(rng, NP=P, page=page, Hkv=Hkv, D=D)
    pt = _pt(B, P)
    T = P * page
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    lay = KV.prefill_write_at(lay, 0, pt, k, v)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    kv_len, win = 30, 8
    got = _attend(lay, pt, jnp.asarray([kv_len]), q, window=win)
    want = flash_attention(q, k[:, kv_len - win:kv_len],
                           v[:, kv_len - win:kv_len], causal=False)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_int8_kv_quantization_error(rng):
    B, S, Hq, Hkv, D, page = 2, 32, 4, 2, 64, 8
    P = S // page
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    pt = _pt(B, P)

    lay8 = _layer(rng, NP=B * P, page=page, Hkv=Hkv, D=D, dtype=jnp.int8)
    lay8 = KV.prefill_write_at(lay8, 0, pt, k, v)
    got = _attend(lay8, pt, jnp.full(B, S, jnp.int32), q)
    want = flash_attention(q, k, v, causal=False)
    # int8 per-(slot, head) scales: ~1% relative error budget
    rel = np.abs(np.asarray(got) - np.asarray(want)).max() / \
        np.abs(np.asarray(want)).max()
    assert rel < 0.05, rel


def test_holes_are_dropped(rng):
    """Unmapped pages (-1) neither write nor contribute to attention."""
    B, Hkv, D, page, P = 1, 1, 16, 4, 4
    lay = _layer(rng, NP=P, page=page, Hkv=Hkv, D=D)
    pt = jnp.asarray([[0, -1, 1, -1]], jnp.int32)
    S = P * page
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    before = np.asarray(lay.k).copy()
    lay = KV.prefill_write_at(lay, 0, pt, k, v)
    after = np.asarray(lay.k)
    # pages 2, 3 of the heap were never mapped → untouched
    np.testing.assert_array_equal(after[0, 2:], before[0, 2:])

    q = jnp.asarray(rng.standard_normal((B, 1, 2, D)), jnp.float32)
    out = _attend(lay, pt, jnp.asarray([S]), q)
    # equivalent dense attention over the mapped positions only
    sel = np.r_[0:4, 8:12]
    want = flash_attention(q, k[:, sel], v[:, sel], causal=False)
    np.testing.assert_allclose(out, want, atol=2e-2, rtol=2e-2)


def test_kv_allocator_page_space(rng):
    """The Ouroboros-backed page-id allocator grants exactly the page
    space and recycles freed ids."""
    ouro, wpp, physical = KV.make_kv_allocator(num_pages=64)
    st = ouro.init()
    sizes = jnp.full(64, 256, jnp.int32)
    st, offs = ouro.alloc(st, sizes, jnp.ones(64, bool))
    ids = np.asarray(offs) // wpp
    good = ids[np.asarray(offs) >= 0]
    assert len(np.unique(good)) == len(good)
    assert len(good) == 64
    # every granted id addresses the physical page array
    assert (good < physical).all()
    st = ouro.free(st, offs, sizes, jnp.ones(64, bool))
    st, offs2 = ouro.alloc(st, sizes, jnp.ones(64, bool))
    assert (np.asarray(offs2) >= 0).sum() >= (np.asarray(offs) >= 0).sum()


@pytest.mark.slow
def test_ring_page_table_window(rng):
    """Ring tables: a window-bounded table serves an unbounded sequence
    (slot = page mod P); attention over the ring equals dense attention
    over the window at every step."""
    B, Hq, Hkv, D, page = 1, 2, 1, 16, 8
    window = 16
    P = window // page + 2          # 4 slots — sequence runs to 6 pages
    T = 48
    lay = _layer(rng, NP=P, page=page, Hkv=Hkv, D=D)
    pt = _pt(B, P)                  # all P physical pages mapped
    k = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)

    for t in range(T):
        lay = KV.append1_at(lay, 0, pt, jnp.asarray([t]), k[:, t:t + 1],
                            v[:, t:t + 1], ring=True)
        kv_len = t + 1
        got = _attend(lay, pt, jnp.asarray([kv_len]), q, window=window,
                      ring=True)
        lo = max(0, kv_len - window)
        want = flash_attention(q, k[:, lo:kv_len], v[:, lo:kv_len],
                               causal=False)
        np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2,
                                   err_msg=f"step {t}")


def test_prefill_write_at_canonical_table_is_a_reshape(rng):
    """At the canonical table (page id = b·P + j) a prefill into one
    layer of the stacked heap lays the prompt's K/V out as pages of
    rows (page, Hkv·D), and leaves the other layer untouched."""
    B, S, Hkv, D, page = 2, 24, 2, 16, 8
    P = 4
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    lay = KV.prefill_write_at(
        _layer(rng, NP=B * P, page=page, Hkv=Hkv, D=D, L=2), 1, _pt(B, P),
        k, v)

    def pages(x):
        x = np.pad(np.asarray(x), ((0, 0), (0, P * page - S), (0, 0), (0, 0)))
        return x.reshape(B * P, page, Hkv * D)

    np.testing.assert_array_equal(np.asarray(lay.k[1]), pages(k))
    np.testing.assert_array_equal(np.asarray(lay.v[1]), pages(v))
    assert not np.asarray(lay.k[0]).any() and not np.asarray(lay.v[0]).any()


def _per_layer_step(cfg, params, tokens, mode, kv):
    """The dense stack one layer at a time, each on its own one-layer
    slice of the heap, written back into the stack: the reference for
    the scan that carries the stacked heap.  Returns the last
    position's logits and the advanced cache, as ``Model.prefill`` /
    ``Model.decode_step`` do."""
    B, S = tokens.shape
    pos = (kv.seq_lens[:, None] if mode == "decode" else
           jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S)))
    x = TF.embed(cfg, params, tokens, dtype=jnp.float32)
    heap = kv.layers
    for l in range(cfg.num_layers):
        p_l = jax.tree.map(lambda a: a[l], params["blocks"])
        one = KV.KVLayer(*(None if h is None else h[l:l + 1]
                           for h in heap))
        x, one, _ = TF.dense_block(cfg, p_l, x, pos, mode, one, 0,
                                   kv.page_table, kv.seq_lens)
        heap = KV.KVLayer(*(None if h is None else h.at[l].set(o[0])
                            for h, o in zip(heap, one)))
    logits = TF.unembed(cfg, params, x[:, -1:])[:, 0]
    return logits, kv._replace(layers=heap, seq_lens=kv.seq_lens + S)


@pytest.mark.parametrize("case", ("plain", "ring", "int8"))
def test_carried_heap_matches_per_layer_reference(rng, case):
    """Prefill and decode through the layer scan that carries the
    stacked heap give the per-layer reference's logits and heap
    contents, and on a float32 heap the dense causal forward's logits:
    a plain float32 heap, a sliding window on a ring table that wraps
    (3 table slots, 4 pages of tokens), and an int8 heap with its
    scales."""
    cfg = dataclasses.replace(get_arch("qwen2-0.5b").smoke(), num_layers=3,
                              sliding_window=16 if case == "ring" else None)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, T, S0 = 2, 56, 20
    caches = m.make_decode_caches(
        B, max_seq=64, kv_dtype=jnp.int8 if case == "int8" else jnp.float32,
        window_ring=case == "ring")
    P = caches.kv.page_table.shape[1]
    assert P == (3 if case == "ring" else 4)
    caches = caches._replace(kv=caches.kv._replace(page_table=(
        jnp.arange(B)[:, None] * P + jnp.arange(P)[None]).astype(jnp.int32)))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)

    prefill = jax.jit(lambda c, t: m.prefill(params, {"tokens": t}, c,
                                             dtype=jnp.float32))
    decode = jax.jit(lambda c, t: m.decode_step(params, t, c,
                                                dtype=jnp.float32))
    ref_pre = jax.jit(lambda kv, t: _per_layer_step(cfg, params, t,
                                                    "prefill", kv))
    ref_dec = jax.jit(lambda kv, t: _per_layer_step(cfg, params, t,
                                                    "decode", kv))

    full = TF.forward(cfg, params, toks, mode="train",
                      dtype=jnp.float32)[0]

    def same(got, want):
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-5, atol=1e-5)
        for g, w in zip(got[1].layers, want[1].layers):
            if g is not None:
                np.testing.assert_allclose(
                    np.asarray(g, np.float32), np.asarray(w, np.float32),
                    rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got[1].seq_lens),
                                      np.asarray(want[1].seq_lens))

    want = ref_pre(caches.kv, toks[:, :S0])
    logits, caches = prefill(caches, toks[:, :S0])
    same((logits, caches.kv), want)
    errs = [float(jnp.abs(logits - full[:, S0 - 1]).max())]
    for t in range(S0, T):
        want = ref_dec(want[1], toks[:, t:t + 1])
        logits, caches = decode(caches, toks[:, t:t + 1])
        same((logits, caches.kv), want)
        errs.append(float(jnp.abs(logits - full[:, t]).max()))
    if case != "int8":   # int8 moves these logits by ~20%, as bf16 does
        assert max(errs) < 1e-3 * float(jnp.abs(full).max()), errs
