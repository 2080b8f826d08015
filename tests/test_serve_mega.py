"""Fused decode tick tests (serve/engine.py, DESIGN.md §11).

The contract under test: the tick — the engine's only decode loop —
produces token streams IDENTICAL to each request served alone
(``serve_ref``) — across allocator backends, lowerings, and shard
counts — while executing grow + forward + sample as ONE jitted tick
whose kernel-launch count is independent of ``max_batch``.  Failure
recovery (defrag-retry on page exhaustion, eviction) gives the streams
the same tick produces with the heap unpressured, and the proactive
``defrag_threshold`` trigger rides the same suite.

Everything runs float32 (kv + compute): greedy argmax parity must be
bit-exact, not merely close.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.models.model import build_model
from serve_ref import expected_stream

pytestmark = pytest.mark.serve
ARCH = "qwen2-0.5b"


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_arch(ARCH).smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _prompts(cfg, n_req, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, cfg.vocab_size, int(rng.integers(4, 30)))
            for _ in range(n_req)]


def _run(tiny_model, *, n_req=4, max_new=5, seed=0, eos_id=None, **kw):
    from repro.serve.engine import ServingEngine
    cfg, m, params = tiny_model
    eng = ServingEngine(m, params, max_batch=3, max_seq=96,
                        kv_dtype=jnp.float32, compute_dtype=jnp.float32,
                        **kw)
    for p in _prompts(cfg, n_req, seed):
        eng.submit(p, max_new_tokens=max_new, eos_id=eos_id)
    done = eng.run_until_done(300)
    assert len(done) == n_req
    return {r.uid: r.out_tokens for r in done}, eng


def _expected(cfg, *, n_req=4, max_new=5, seed=0, eos_id=None):
    """uid → the stream of that request served alone."""
    return {uid: expected_stream(ARCH, p, max_new, eos_id)
            for uid, p in enumerate(_prompts(cfg, n_req, seed), 1)}


@pytest.mark.parametrize("backend,lowering,shards", [
    ("jnp", "auto", 1),
    ("jnp", "auto", 4),
    ("pallas", "whole", 1),
    ("pallas", "blocked", 1),
    ("pallas", "auto", 4),
])
def test_mega_matches_host_loop(tiny_model, backend, lowering, shards):
    """Token-for-token: the fused tick gives every request the stream
    it gets served alone, whatever transaction backend/lowering/shard
    count grows the KV heap underneath it."""
    toks, eng = _run(tiny_model, alloc_backend=backend,
                     alloc_lowering=lowering, num_shards=shards)
    assert toks == _expected(tiny_model[0])
    assert eng.stats["frees"] == eng.stats["allocs"]
    assert eng.stats["alloc_failures"] == 0


def test_mega_handles_max_new_one(tiny_model):
    """Finish-semantics edge: ``max_new_tokens=1`` yields TWO tokens
    (prefill token + the decode append that detects the budget); the
    tick's budget accounting must reproduce that, not truncate at
    one."""
    g, _ = _run(tiny_model, n_req=2, max_new=1)
    assert g == _expected(tiny_model[0], n_req=2, max_new=1)
    assert all(len(t) == 2 for t in g.values())


def test_mega_eos_parity(tiny_model):
    """EOS early-exit fires on the tick that decodes it: pick the
    token the reference emits mid-stream as eos_id and rerun."""
    cfg = tiny_model[0]
    eos = _expected(cfg, n_req=2, max_new=6, seed=3)[1][2]
    a = _expected(cfg, n_req=2, max_new=6, seed=3, eos_id=eos)
    b, _ = _run(tiny_model, n_req=2, max_new=6, seed=3, eos_id=eos)
    assert a == b
    assert len(a[1]) < 6  # the eos actually cut request 1 short


def test_mega_launch_count_constant_in_batch(tiny_model):
    """The tentpole claim: launches per fused tick read off the jaxpr
    — exactly ONE pallas_call with alloc_backend="pallas" (the bulk
    grow transaction; decode attention rides the jnp paged path), zero
    with the jnp oracle, and the SAME at any max_batch."""
    from benchmarks.common import launches_per_tick
    from repro.serve.engine import ServingEngine
    cfg, m, params = tiny_model
    counts = {}
    for backend in ("jnp", "pallas"):
        per_batch = []
        for mb in (2, 8):
            eng = ServingEngine(m, params, max_batch=mb, max_seq=96,
                                kv_dtype=jnp.float32,
                                compute_dtype=jnp.float32,
                                alloc_backend=backend)
            n = launches_per_tick(eng)
            assert eng.stats["launches_per_tick"] == n
            per_batch.append(n)
        assert per_batch[0] == per_batch[1], (backend, per_batch)
        counts[backend] = per_batch[0]
    assert counts == {"jnp": 0, "pallas": 1}


def test_mega_rejects_overlong_request(tiny_model):
    """The device token buffer is sized at construction; a submit past
    it must fail loudly at submit time, not corrupt out_buf later."""
    from repro.serve.engine import ServingEngine
    cfg, m, params = tiny_model
    eng = ServingEngine(m, params, max_batch=2, max_seq=64,
                        kv_dtype=jnp.float32, max_new_cap=8)
    with pytest.raises(ValueError, match="max_new_cap"):
        eng.submit(np.arange(2, 10), max_new_tokens=9)


def test_decode_syncs_token_ids_not_logits(tiny_model):
    """The tick and the prefill argmax ON DEVICE: the tick hands the
    host a (B,) uint8 flag vector and keeps (B,) int32 token ids (its
    token buffer is read at retirement), the prefill returns one int32
    id — no output carries a vocab axis."""
    from repro.serve.engine import ServingEngine
    cfg, m, params = tiny_model
    eng = ServingEngine(m, params, max_batch=3, max_seq=96,
                        kv_dtype=jnp.float32, max_new_cap=8)
    eng._build_mega()
    _, _, ms, flags, *lanes = jax.eval_shape(
        eng._mega_fn, params, eng.alloc_state, eng.caches,
        eng.mega_state)
    assert flags.shape == (3,) and flags.dtype == jnp.uint8
    assert ms.last_tok.shape == (3,) and ms.last_tok.dtype == jnp.int32
    assert ms.out_buf.shape == (3, 8) and ms.out_buf.dtype == jnp.int32
    ids, _ = jax.eval_shape(eng._prefill, params,
                            {"tokens": jnp.zeros((1, 6), jnp.int32)},
                            eng.caches, np.int32(0))
    assert ids.shape == (1,) and ids.dtype == jnp.int32
    assert all(cfg.vocab_size not in x.shape
               for x in jax.tree.leaves((ms, flags, lanes, ids)))


@pytest.mark.defrag
def test_mega_recovers_from_exhaustion(tiny_model):
    """The exhaustion-recovery trace of test_defrag: alloc failure
    surfaces in the per-tick flags, the host reclaims the failed
    slots' partial grants, runs a defrag wave, and the retried ticks
    produce the SAME streams the tick gives on an unpressured heap."""
    from repro.serve.engine import ServingEngine
    cfg, m, params = tiny_model

    def trace(pressure):
        eng = ServingEngine(m, params, max_batch=2, max_seq=64,
                            kv_dtype=jnp.float32,
                            compute_dtype=jnp.float32, num_pages=16)
        if pressure:
            n = 16
            big = jnp.full(n, 2048, jnp.int32)
            st, offs = eng.ouro.alloc(eng.alloc_state, big,
                                      jnp.ones(n, bool))
            granted = np.asarray(offs) >= 0
            assert granted.any()
            eng.alloc_state = eng.ouro.free(st, offs, big,
                                            jnp.asarray(granted))
        rng = np.random.default_rng(1)
        for _ in range(2):
            eng.submit(rng.integers(2, cfg.vocab_size, 40),
                       max_new_tokens=8)
        done = eng.run_until_done(100)
        assert len(done) == 2
        return sorted(tuple(r.out_tokens) for r in done), eng

    h, plain = trace(False)
    g, eng = trace(True)
    assert h == g
    assert plain.stats["defrag_waves"] == 0
    assert eng.stats["defrag_waves"] > 0
    assert eng.stats["frees"] == eng.stats["allocs"]


@pytest.mark.defrag
def test_exhaustion_evicts_instead_of_raising(tiny_model):
    """When defrag cannot reclaim (a co-tenant HOLDS the heap live),
    the tick degrades gracefully instead of raising MemoryError: the
    youngest slot is evicted (its pages freed, its request requeued,
    ``evictions`` counted) and the engine stays serviceable — once the
    co-tenant releases its pages, the evicted request replays and
    completes with the stream an unpressured engine gives it."""
    from repro.serve.engine import ServingEngine
    cfg, m, params = tiny_model
    prompt = np.random.default_rng(1).integers(2, cfg.vocab_size, 30)

    def engine():
        return ServingEngine(m, params, max_batch=2, max_seq=64,
                             kv_dtype=jnp.float32,
                             compute_dtype=jnp.float32, num_pages=16)

    def run():
        eng = engine()
        # co-tenant takes every 256 B page, hands exactly 2 back:
        # enough to admit a 30-token prompt, not enough to grow.
        sizes = jnp.full(64, 256, jnp.int32)
        eng.alloc_state, offs = eng.ouro.alloc(
            eng.alloc_state, sizes, jnp.ones(64, bool))
        offs = np.asarray(offs)
        held = offs[offs >= 0]
        back = np.full(64, -1, np.int32)
        back[:2] = held[:2]
        eng.alloc_state = eng.ouro.free(
            eng.alloc_state, jnp.asarray(back), sizes,
            jnp.asarray(back >= 0))
        eng.submit(prompt, max_new_tokens=30)
        # serve into the wall: no exception, eviction(s) instead, and
        # the request is parked (requeued or re-admitted), not lost
        for _ in range(30):
            assert eng.step() == []
        assert eng.stats["evictions"] > 0
        assert (len(eng.waiting)
                + sum(r is not None for r in eng.slot_req)) == 1
        # co-tenant releases the heap → the evicted request replays
        rest = np.full(64, -1, np.int32)
        rest[:len(held) - 2] = held[2:]
        eng.alloc_state = eng.ouro.free(
            eng.alloc_state, jnp.asarray(rest), sizes,
            jnp.asarray(rest >= 0))
        done = eng.run_until_done(200)
        assert len(done) == 1 and done[0].out_tokens
        return done[0].out_tokens

    plain = engine()
    plain.submit(prompt, max_new_tokens=30)
    want = plain.run_until_done(200)[0].out_tokens
    assert plain.stats["evictions"] == 0
    assert run() == want


@pytest.mark.defrag
def test_auto_defrag_threshold_trigger(tiny_model):
    """S1: past ``defrag_threshold`` the engine fires a proactive
    defrag wave mid-serve (counted in ``auto_defrag_waves``); with the
    default ``None`` it never does."""
    from repro.serve.engine import ServingEngine
    cfg, m, params = tiny_model
    for thresh, fires in ((0.05, True), (None, False)):
        eng = ServingEngine(m, params, max_batch=2, max_seq=64,
                            kv_dtype=jnp.float32, num_pages=32,
                            defrag_threshold=thresh)
        # checkerboard co-tenant: free every other page → high
        # frag_ratio that persists while the engine serves
        sizes = jnp.full(32, 256, jnp.int32)
        eng.alloc_state, offs = eng.ouro.alloc(
            eng.alloc_state, sizes, jnp.ones(32, bool))
        offs = np.asarray(offs)
        odd = (np.arange(32) % 2 == 0) & (offs >= 0)
        eng.alloc_state = eng.ouro.free(eng.alloc_state,
                                        jnp.asarray(offs), sizes,
                                        jnp.asarray(odd))
        eng.submit(np.arange(2, 20) % cfg.vocab_size, max_new_tokens=4)
        eng.run_until_done(50)
        assert (eng.stats["auto_defrag_waves"] >= 1) == fires


def test_engine_validates_defrag_knobs(tiny_model):
    from repro.serve.engine import ServingEngine
    with pytest.raises(ValueError, match="defrag_threshold"):
        ServingEngine(None, None, defrag_threshold=1.5)
    with pytest.raises(ValueError, match="defrag_check_interval"):
        ServingEngine(None, None, defrag_check_interval=0)
    with pytest.raises(ValueError, match="max_new_cap"):
        ServingEngine(None, None, max_new_cap=0)


def test_engine_refuses_the_host_loop():
    """The fused tick is the only decode loop: asking for the removed
    host loop fails at construction, before any set-up."""
    from repro.serve.engine import ServingEngine
    with pytest.raises(ValueError, match="host decode loop"):
        ServingEngine(None, None, mega_step=False)
