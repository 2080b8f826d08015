"""Defragmentation subsystem lockdown (core/defrag.py, DESIGN.md §10).

Four contracts:

1. **Reclamation** — after a randomized churn trace that strands ≥ 30 %
   of physical pages in sparsely-occupied bound chunks, ONE
   ``Ouroboros.defrag`` wave migrates the stragglers into a dense
   prefix: bound chunks drop to the minimum that holds the live pages,
   emptied chunks retire to the pool, the largest free extent becomes
   chunk-sized again, and an allocation that failed before the wave
   succeeds after it.

2. **Parity** — the migration execute step is bit-identical, word for
   word across the whole arena, between the jnp replay oracle and both
   Pallas lowerings (whole + region-blocked), for ``num_shards ∈ {1,
   4}``; each wave is ONE ``pallas_call`` (asserted on the jaxpr), and
   cross-shard rebalance waves ride the same kernel.

3. **Forwarding** — callers' references survive: ``forward_offsets``
   remaps granted offsets so ``check_pattern`` still passes word for
   word, and the paged KV cache's ``apply_forwarding`` keeps
   post-remap reads identical to pre-defrag reads.

4. **Serving** — the engine coalesces decode-step page growth into one
   transaction, retries through a defrag wave instead of raising
   ``MemoryError``, rebalances shards past the imbalance threshold,
   and surfaces ``defrag_waves``/``pages_migrated``/``frag_ratio``.

The ``compact()`` chunk-rebind path (the §5b predecessor) is locked
down here too, across the same implementation matrix — it was
previously untested against the Pallas lowerings.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import HeapConfig, Ouroboros, defrag, shards
from repro.kernels.ops import count_pallas_calls

pytestmark = pytest.mark.defrag

# 16 chunks of 512 words; min page 64 B → class-0 chunks hold 32 pages,
# so a churn trace spreads live pages over many chunks quickly.
CFG = HeapConfig(total_bytes=1 << 15, chunk_bytes=1 << 11,
                 min_page_bytes=64)
# four of the above per shard
SH_CFG = HeapConfig(total_bytes=1 << 17, chunk_bytes=1 << 11,
                    min_page_bytes=64)
SHARDS = 4
N = 16
PAGE = 64  # class-0 page bytes

CHUNK_VARIANTS = ("chunk", "va_chunk", "vl_chunk")
LOWERINGS = ("whole", "blocked")


def _impls(cfg, variant, **kw):
    return [("jnp", Ouroboros(cfg, variant, **kw)),
            ("pallas/whole", Ouroboros(cfg, variant, backend="pallas",
                                       lowering="whole", **kw)),
            ("pallas/blocked", Ouroboros(cfg, variant, backend="pallas",
                                         lowering="blocked", **kw))]


def _assert_lockstep(variant, tag, states):
    ref = jax.tree.leaves(states[0][1])
    for lbl, st in states[1:]:
        for a, b in zip(ref, jax.tree.leaves(st)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{variant}: {lbl} diverged from the oracle "
                        f"at {tag}")


def _churn(ouro, state, seed, rounds=14, keep_every=5, shard_hint=None,
           until_full=False):
    """Randomized alloc/free churn leaving scattered live pages.
    ``until_full`` keeps allocating until the heap exhausts (every
    chunk bound) before the free phase.  Returns (state, kept)."""
    rng = np.random.default_rng(seed)
    sizes = jnp.full(N, PAGE, jnp.int32)
    live = []
    kw = {}
    if shard_hint is not None:
        kw["shard_hint"] = jnp.full(N, shard_hint, jnp.int32)
    fails = 0
    for step in range(200):
        if until_full:
            if fails >= 2:
                break
        elif step >= rounds:
            break
        mask = jnp.asarray(rng.random(N) < 0.95)
        state, offs = ouro.alloc(state, sizes, mask, **kw)
        got = [int(o) for o in np.asarray(offs) if o >= 0]
        fails = fails + 1 if not got else 0
        live.extend(got)
    keep_idx = set(range(0, len(live), keep_every))
    kept = [o for i, o in enumerate(live) if i in keep_idx]
    drop = [o for i, o in enumerate(live) if i not in keep_idx]
    rng.shuffle(drop)
    for i in range(0, len(drop), N):
        b = drop[i:i + N]
        fo = np.full(N, -1, np.int32)
        fo[:len(b)] = b
        state = ouro.free(state, jnp.asarray(fo), sizes,
                          jnp.asarray(fo >= 0))
    return state, kept


def _bound_chunks(ouro, state):
    from repro.core import arena
    if ouro.num_shards == 1:
        _, _, meta = arena.unpack(ouro.layout, state)
        return np.asarray(meta.chunk_class)
    lay = ouro.layout.shard
    out = []
    for s in range(ouro.num_shards):
        _, _, meta = arena.unpack(
            lay, arena.Arena(state.mem[s], state.ctl[s]))
        out.append(np.asarray(meta.chunk_class))
    return np.concatenate(out)


# --------------------------------------------------------------------------
# 1. reclamation: churn → strand → one wave → dense prefix
# --------------------------------------------------------------------------

def test_defrag_reclaims_stranded_pages():
    """The acceptance trace: randomized churn strands ≥ 30 % of the
    physical pages (free words locked inside sparsely-occupied bound
    chunks); one wave migrates the stragglers into a dense prefix,
    retires the emptied chunks, restores a chunk-sized free extent,
    and un-fails a chunk-sized allocation — with every surviving
    allocation's data intact through the forwarding remap."""
    ouro = Ouroboros(CFG, "vl_chunk")
    state, kept = _churn(ouro, ouro.init(), seed=0, until_full=True)
    n_live = len(kept)
    ppc = CFG.pages_per_chunk(0)

    # tag the survivors before the wave
    lanes = ((n_live + N - 1) // N) * N
    ko = np.full(lanes, -1, np.int32)
    ko[:n_live] = kept
    sizes = jnp.full(lanes, PAGE, jnp.int32)
    tags = jnp.arange(1000, 1000 + lanes, dtype=jnp.int32)
    state = ouro.write_pattern(state, jnp.asarray(ko), sizes, tags)

    # stranding: ≥ 30 % of physical pages are free-but-locked inside
    # bound chunks, and a chunk-sized allocation fails despite them
    cc = _bound_chunks(ouro, state)
    n_bound = int((cc >= 0).sum())
    stranded_pages = n_bound * ppc - n_live
    total_pages = CFG.total_words // CFG.page_words(0)
    assert stranded_pages / total_pages >= 0.30, (
        f"churn stranded only {stranded_pages}/{total_pages} pages")
    big = jnp.full(4, CFG.chunk_bytes, jnp.int32)
    state, big_offs = ouro.alloc(state, big, jnp.ones(4, bool))
    assert (np.asarray(big_offs) < 0).all(), (
        "heap not actually exhausted for chunk-sized requests")
    fr0 = float(ouro.frag_stats(state)["frag_ratio"])

    state, fwd = ouro.defrag(state)
    moves = int((np.asarray(fwd.src) >= 0).sum())
    assert moves > 0

    # dense prefix: minimal bound chunks, everything else in the pool
    cc2 = _bound_chunks(ouro, state)
    assert int((cc2 >= 0).sum()) == -(-n_live // ppc), (
        "wave left more bound chunks than the live pages need")
    fs = ouro.frag_stats(state)
    assert int(fs["largest_free_extent"]) >= CFG.words_per_chunk
    assert float(fs["frag_ratio"]) < fr0

    # survivors are intact at their forwarded offsets
    ko2 = np.asarray(defrag.forward_offsets(fwd, jnp.asarray(ko)))
    ok = np.asarray(ouro.check_pattern(state, jnp.asarray(ko2), sizes,
                                       tags))
    assert ok[:n_live].all(), "migration corrupted live words"

    # the failed chunk-sized allocation now succeeds
    state, big_offs = ouro.alloc(state, big, jnp.ones(4, bool))
    assert (np.asarray(big_offs) >= 0).any(), (
        "defrag failed to reclaim a chunk-sized extent")


def test_page_kind_defrag_is_noop():
    ouro = Ouroboros(CFG, "page")
    st = ouro.init()
    st2, fwd = ouro.defrag(st)
    assert int((np.asarray(fwd.src) >= 0).sum()) == 0
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_defrag_knobs_validated():
    with pytest.raises(ValueError, match="max_moves"):
        o = Ouroboros(CFG, "vl_chunk")
        o.defrag(o.init(), max_moves=0)
    with pytest.raises(ValueError, match="rebalance"):
        o = Ouroboros(CFG, "vl_chunk")
        o.rebalance(o.init())


# --------------------------------------------------------------------------
# 2. parity: jnp oracle vs both lowerings, single and sharded
# --------------------------------------------------------------------------

@pytest.mark.compiled_lowering
@pytest.mark.parametrize("variant", CHUNK_VARIANTS)
def test_defrag_parity_across_lowerings(variant):
    """Churn → wave → more churn → wave, in lockstep: identical
    forwarding tables and word-identical arenas after every wave."""
    impls = _impls(CFG, variant)
    states = [(lbl, o.init()) for lbl, o in impls]
    for round_ in range(2):
        states = [(lbl, _churn(o, st, seed=round_)[0])
                  for (lbl, o), (_, st) in zip(impls, states)]
        outs = [(lbl, o.defrag(st, max_moves=64))
                for (lbl, o), (_, st) in zip(impls, states)]
        ref_fwd = outs[0][1][1]
        for lbl, (_, fwd) in outs[1:]:
            for a, b in zip(ref_fwd, fwd):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"{variant}/{lbl}: forwarding diverged at "
                            f"wave {round_}")
        states = [(lbl, st) for lbl, (st, _) in outs]
        _assert_lockstep(variant, f"wave {round_}", states)


@pytest.mark.compiled_lowering
@pytest.mark.parametrize("variant", ("chunk", "vl_chunk"))
def test_sharded_defrag_parity(variant):
    """num_shards=4: one wave defragments every shard, still ONE kernel,
    bit-identical across the implementation matrix."""
    impls = _impls(SH_CFG, variant, num_shards=SHARDS)
    states = []
    for lbl, o in impls:
        st = o.init()
        st, _ = _churn(o, st, seed=1, shard_hint=0)
        st, _ = _churn(o, st, seed=2, shard_hint=2)
        states.append((lbl, st))
    outs = [(lbl, o.defrag(st, max_moves=64))
            for (lbl, o), (_, st) in zip(impls, states)]
    ref_fwd = outs[0][1][1]
    for lbl, (_, fwd) in outs[1:]:
        np.testing.assert_array_equal(
            np.asarray(ref_fwd.src), np.asarray(fwd.src),
            err_msg=f"{variant}/{lbl}: sharded forwarding diverged")
    states = [(lbl, st) for lbl, (st, _) in outs]
    _assert_lockstep(variant, "sharded wave", states)


@pytest.mark.compiled_lowering
def test_rebalance_parity_and_load_shift():
    """Cross-shard rebalance: bit-identical across the matrix, moves
    live words from the most- to the least-loaded shard (claiming pool
    chunks on the receiver), and survivors stay word-intact through
    the forwarding remap."""
    impls = _impls(SH_CFG, "vl_chunk", num_shards=SHARDS)
    states, kept = [], None
    for lbl, o in impls:
        st = o.init()
        st, k0 = _churn(o, st, seed=3, shard_hint=0)
        states.append((lbl, st))
        kept = k0
    lanes = ((len(kept) + N - 1) // N) * N
    ko = np.full(lanes, -1, np.int32)
    ko[:len(kept)] = kept
    sizes = jnp.full(lanes, PAGE, jnp.int32)
    tags = jnp.arange(500, 500 + lanes, dtype=jnp.int32)
    states = [(lbl, o.write_pattern(st, jnp.asarray(ko), sizes, tags))
              for (lbl, o), (_, st) in zip(impls, states)]

    m0, c0 = (np.asarray(states[0][1].mem), np.asarray(states[0][1].ctl))
    lw0 = np.asarray(shards.shard_live_words(SH_CFG, SHARDS, "chunk",
                                             "vl", m0, c0))
    outs = [(lbl, o.rebalance(st, max_moves=64))
            for (lbl, o), (_, st) in zip(impls, states)]
    ref_st, ref_fwd = outs[0][1]
    for lbl, (st, fwd) in outs[1:]:
        np.testing.assert_array_equal(
            np.asarray(ref_fwd.src), np.asarray(fwd.src),
            err_msg=f"{lbl}: rebalance plan diverged")
    _assert_lockstep("vl_chunk", "rebalance",
                     [(lbl, st) for lbl, (st, _) in outs])

    assert int((np.asarray(ref_fwd.src) >= 0).sum()) > 0
    lw1 = np.asarray(shards.shard_live_words(
        SH_CFG, SHARDS, "chunk", "vl", np.asarray(ref_st.mem),
        np.asarray(ref_st.ctl)))
    donor, recv = int(np.argmax(lw0)), int(np.argmin(lw0))
    assert lw1[donor] < lw0[donor] and lw1[recv] > lw0[recv], (
        f"load did not shift donor→receiver: {lw0} → {lw1}")
    ko2 = np.asarray(defrag.forward_offsets(ref_fwd, jnp.asarray(ko)))
    assert (ko2 != ko).any(), "rebalance left every kept page in place"
    ok = np.asarray(impls[0][1].check_pattern(ref_st, jnp.asarray(ko2),
                                              sizes, tags))
    assert ok[:len(kept)].all(), "rebalance corrupted live words"


@pytest.mark.compiled_lowering
@pytest.mark.parametrize("lowering", LOWERINGS)
@pytest.mark.parametrize("num_shards", (1, SHARDS))
def test_single_pallas_call_per_wave(lowering, num_shards):
    """A migration wave — plan AND execute — lowers to exactly one
    pallas_call under backend="pallas" (both lowerings, sharded or
    not); the jnp oracle lowers to zero.  Rebalance rides the same
    kernel."""
    cfg = SH_CFG if num_shards > 1 else CFG
    for backend, want in (("pallas", 1), ("jnp", 0)):
        o = Ouroboros(cfg, "vl_chunk", backend, lowering,
                      num_shards=num_shards)
        st = o.init()
        j = jax.make_jaxpr(lambda s: o.defrag(s, max_moves=32))(st)
        assert count_pallas_calls(j) == want, (
            f"{backend}/{lowering}/shards{num_shards}: defrag wave is "
            f"not a single fused kernel")
        if num_shards > 1:
            j = jax.make_jaxpr(lambda s: o.rebalance(s, max_moves=32))(
                st)
            assert count_pallas_calls(j) == want, (
                f"{backend}/{lowering}: rebalance wave is not a single "
                f"fused kernel")


# --------------------------------------------------------------------------
# 3. the compact() chunk-rebind path across the same matrix (satellite)
# --------------------------------------------------------------------------

@pytest.mark.compiled_lowering
@pytest.mark.parametrize("variant", CHUNK_VARIANTS)
def test_compact_lockstep_across_lowerings(variant):
    """compact() interleaved mid-trace: states built by the Pallas
    lowerings stay word-identical to the oracle through the rebind and
    keep serving identical grants afterwards (previously compact was
    only exercised on jnp-built states)."""
    impls = _impls(CFG, variant)
    states = [(lbl, o.init()) for lbl, o in impls]
    sizes = jnp.full(N, PAGE, jnp.int32)
    ones = jnp.ones(N, bool)
    for round_ in range(3):
        outs = [o.alloc(st, sizes, ones)
                for (_, o), (_, st) in zip(impls, states)]
        offs0 = np.asarray(outs[0][1])
        for (lbl, _), (_, offs) in zip(impls[1:], outs[1:]):
            np.testing.assert_array_equal(offs0, np.asarray(offs))
        states = [(lbl, st)
                  for (lbl, _), (st, _) in zip(impls, outs)]
        fo = np.where(offs0 >= 0, offs0, -1).astype(np.int32)
        half = jnp.asarray(np.arange(N) % 2 == 0) & jnp.asarray(fo >= 0)
        states = [(lbl, o.free(st, jnp.asarray(fo), sizes, half))
                  for (lbl, o), (_, st) in zip(impls, states)]
        states = [(lbl, o.compact(st))
                  for (lbl, o), (_, st) in zip(impls, states)]
        _assert_lockstep(variant, f"compact {round_}", states)


@pytest.mark.compiled_lowering
def test_sharded_compact_lockstep():
    impls = _impls(SH_CFG, "vl_chunk", num_shards=SHARDS)
    states = [(lbl, _churn(o, o.init(), seed=5, shard_hint=1)[0])
              for lbl, o in impls]
    states = [(lbl, o.compact(st))
              for (lbl, o), (_, st) in zip(impls, states)]
    _assert_lockstep("vl_chunk", "sharded compact", states)
    sizes = jnp.full(N, PAGE, jnp.int32)
    outs = [o.alloc(st, sizes, jnp.ones(N, bool))
            for (_, o), (_, st) in zip(impls, states)]
    offs0 = np.asarray(outs[0][1])
    for (lbl, _), (_, offs) in zip(impls[1:], outs[1:]):
        np.testing.assert_array_equal(offs0, np.asarray(offs),
                                      err_msg=f"{lbl} post-compact")


# --------------------------------------------------------------------------
# 4. forwarding consumers: KV cache remap
# --------------------------------------------------------------------------

def test_kv_apply_forwarding_preserves_reads():
    """Paged-KV reads through the page table are word-identical before
    and after a defrag remap (rows moved + table rewritten in one
    step)."""
    from repro.paged import kv_cache as KV
    rng = np.random.default_rng(0)
    L, NP, B, P, H, D = 2, 8, 2, 3, 1, 4
    kv = KV.init_paged_kv(L, NP, B, P, H, D, kv_dtype=jnp.float32)
    kv = kv._replace(
        layers=kv.layers._replace(
            k=jnp.asarray(rng.standard_normal(kv.layers.k.shape),
                          jnp.float32),
            v=jnp.asarray(rng.standard_normal(kv.layers.v.shape),
                          jnp.float32)),
        page_table=jnp.asarray([[5, 2, -1], [7, -1, -1]], jnp.int32),
        seq_lens=jnp.asarray([40, 16], jnp.int32))

    def gather(kv):
        pt = jnp.maximum(kv.page_table, 0)
        ok = (kv.page_table >= 0)[None, :, :, None, None]
        return np.asarray(jnp.where(ok, kv.layers.k[:, pt], 0.0))

    before = gather(kv)
    wpp = 64
    fwd = defrag.Forwarding(
        src=jnp.asarray([5 * wpp, 7 * wpp, -1], jnp.int32),
        dst=jnp.asarray([0 * wpp, 1 * wpp, -1], jnp.int32),
        sizes=jnp.asarray([256, 256, 0], jnp.int32))
    kv2 = KV.apply_forwarding(kv, fwd, wpp)
    np.testing.assert_array_equal(
        np.asarray(kv2.page_table),
        np.asarray([[0, 2, -1], [1, -1, -1]], np.int32))
    np.testing.assert_array_equal(gather(kv2), before)


def test_forward_offsets_passthrough():
    fwd = defrag.Forwarding(src=jnp.asarray([64, -1], jnp.int32),
                            dst=jnp.asarray([0, -1], jnp.int32),
                            sizes=jnp.asarray([256, 0], jnp.int32))
    offs = jnp.asarray([64, 128, -1], jnp.int32)
    got = np.asarray(defrag.forward_offsets(fwd, offs))
    np.testing.assert_array_equal(got, [0, 128, -1])


# --------------------------------------------------------------------------
# 5. fragmentation observability
# --------------------------------------------------------------------------

def test_frag_stats_track_stranding_and_recovery():
    ouro = Ouroboros(CFG, "vl_chunk")
    st = ouro.init()
    fs0 = ouro.frag_stats(st)
    assert int(fs0["free_words"]) > 0
    st, _ = _churn(ouro, st, seed=7)
    fs1 = ouro.frag_stats(st)
    assert float(fs1["frag_ratio"]) > float(fs0["frag_ratio"])
    st, _ = ouro.defrag(st)
    fs2 = ouro.frag_stats(st)
    assert float(fs2["frag_ratio"]) < float(fs1["frag_ratio"])
    assert int(fs2["largest_free_extent"]) >= CFG.words_per_chunk


def test_frag_stats_sharded_shapes():
    ouro = Ouroboros(SH_CFG, "vl_chunk", num_shards=SHARDS)
    fs = ouro.frag_stats(ouro.init())
    assert fs["free_words"].shape == (SHARDS,)
    assert fs["frag_ratio"].shape == (SHARDS,)


def test_frag_stats_page_kind():
    ouro = Ouroboros(CFG, "page")
    st = ouro.init()
    fs = ouro.frag_stats(st)
    assert int(fs["free_words"]) > 0
    # drain class 0 entirely: the largest grantable extent shrinks only
    # if every bigger class drained too — here it stays chunk-sized
    assert int(fs["largest_free_extent"]) == CFG.words_per_chunk


# --------------------------------------------------------------------------
# 6. serving engine: coalesced growth, defrag-on-failure, rebalance
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import get_arch
    from repro.models.model import build_model
    cfg = get_arch("qwen2-0.5b").smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def test_engine_survives_exhaustion_trace(tiny_model, rng):
    """A heap-exhaustion trace that previously raised
    ``MemoryError("KV heap exhausted mid-flight")``: a co-tenant binds
    most chunks to a large size class through the same allocator and
    releases them — sticky bindings strand the chunks for the engine's
    256 B pages.  The engine now reclaims them with a defrag wave and
    finishes every request."""
    cfg, m, params = tiny_model
    from repro.serve.engine import ServingEngine
    eng = ServingEngine(m, params, max_batch=2, max_seq=64,
                        kv_dtype=jnp.float32, num_pages=16)
    n = 16
    big = jnp.full(n, 2048, jnp.int32)
    st, offs = eng.ouro.alloc(eng.alloc_state, big, jnp.ones(n, bool))
    granted = np.asarray(offs) >= 0
    assert granted.any()
    eng.alloc_state = eng.ouro.free(st, offs, big, jnp.asarray(granted))

    for _ in range(2):
        eng.submit(rng.integers(2, cfg.vocab_size, 40), max_new_tokens=8)
    done = eng.run_until_done(100)
    assert len(done) == 2
    assert all(len(r.out_tokens) == 8 for r in done)
    assert eng.stats["alloc_failures"] > 0, (
        "trace never exhausted the heap — nothing was tested")
    assert eng.stats["defrag_waves"] > 0
    assert eng.stats["frag_ratio"] is not None


def test_engine_decode_growth_is_one_transaction(tiny_model, rng):
    """Decode-step page growth coalesces across the active batch: a
    step where EVERY slot crosses a page boundary issues exactly ONE
    bulk alloc transaction (previously one per slot)."""
    cfg, m, params = tiny_model
    from repro.serve.engine import ServingEngine
    eng = ServingEngine(m, params, max_batch=3, max_seq=96,
                        kv_dtype=jnp.float32)
    # identical prompt lengths → the slots cross page boundaries in
    # the same step (page = 16 tokens; admit leaves slot_len = 15)
    for _ in range(3):
        eng.submit(rng.integers(2, cfg.vocab_size, 14),
                   max_new_tokens=8)
    eng.step()  # admission
    crossed = False
    for _ in range(6):
        before = eng.stats["alloc_txns"]
        grants_before = eng.stats["allocs"]
        eng.step()
        txns = eng.stats["alloc_txns"] - before
        grants = eng.stats["allocs"] - grants_before
        assert txns <= 1, (
            f"decode step issued {txns} alloc transactions for one "
            f"batch")
        if grants >= 3:
            crossed = True  # all three slots grew in ONE transaction
    assert crossed, "no step grew all three slots together"


def test_engine_rebalance_trigger_and_output_parity(tiny_model, rng):
    """Sharded engine past the imbalance threshold: a rebalance wave
    fires, live pages spread across shards, and greedy outputs stay
    IDENTICAL to an engine that never rebalances (the KV remap is
    invisible to decoding)."""
    cfg, m, params = tiny_model
    from repro.serve.engine import ServingEngine
    prompt = rng.integers(2, cfg.vocab_size, 30)

    eng = ServingEngine(m, params, max_batch=2, max_seq=96,
                        kv_dtype=jnp.float32, compute_dtype=jnp.float32,
                        num_shards=2, rebalance_threshold=1)
    eng.submit(prompt, max_new_tokens=10)  # slot 0 → shard 0 only
    done = eng.run_until_done(100)
    assert len(done) == 1
    assert eng.stats["rebalance_waves"] > 0, (
        "imbalance never triggered a rebalance wave")
    assert eng.stats["pages_migrated"] > 0

    ref = ServingEngine(m, params, max_batch=2, max_seq=96,
                        kv_dtype=jnp.float32, compute_dtype=jnp.float32,
                        num_shards=2)
    ref.submit(prompt, max_new_tokens=10)
    ref_done = ref.run_until_done(100)
    assert done[0].out_tokens == ref_done[0].out_tokens, (
        "rebalancing changed decoded tokens — the KV remap leaked")


def test_engine_validates_rebalance_threshold():
    from repro.serve.engine import ServingEngine
    with pytest.raises(ValueError, match="rebalance_threshold"):
        ServingEngine(None, None, rebalance_threshold=4)
    with pytest.raises(ValueError, match="rebalance_threshold"):
        ServingEngine(None, None, num_shards=2, rebalance_threshold=0)


def test_engine_surfaces_frag_stats(tiny_model, rng):
    cfg, m, params = tiny_model
    from repro.serve.engine import ServingEngine
    eng = ServingEngine(m, params, max_batch=2, max_seq=64,
                        kv_dtype=jnp.float32, num_shards=2)
    assert isinstance(eng.stats["frag_ratio"], list)
    assert len(eng.stats["free_words"]) == 2
    eng.submit(rng.integers(2, cfg.vocab_size, 8), max_new_tokens=3)
    eng.run_until_done(50)
    fs = eng.refresh_frag_stats()
    assert all(x >= 0 for x in eng.stats["largest_free_extent"])
    assert fs is not None
