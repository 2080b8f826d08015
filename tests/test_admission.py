"""Admission prefill tests (serve/engine.py ``_admit``, ``put_row``).

The contract under test: admitting a request computes ONE row — a
``(1, lp)`` prefill through a view of the caches that holds the shared
page heap and the admitted slot's page-table row — and writes that row
back at the slot, which the prefill program takes as a traced argument.
So every family's streams equal a standalone unpadded prefill of the
prompt followed by greedy decode, in any slot and in both decode loops;
an admission leaves every other slot's heap words, page-table row,
``seq_lens`` and state rows as they were; one program serves every slot
of a prompt length; and the engine counts the rows and token-rows its
prefills computed.

Everything runs float32 (kv + compute): greedy streams must be exact.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.obs import trace as obs_trace
from repro.serve.replay import engine_factory

pytestmark = pytest.mark.serve

FAMILIES = ["qwen2-0.5b", "mixtral-8x7b", "mamba2-780m",
            "recurrentgemma-9b", "seamless-m4t-large-v2"]
MAX_SEQ = 96


@functools.lru_cache(maxsize=None)
def _factory(arch):
    return engine_factory(arch, max_seq=MAX_SEQ)


@functools.lru_cache(maxsize=None)
def _reference_programs(arch):
    """The engine's model and weights, with a jitted prefill and decode."""
    eng = _factory(arch)[1]()
    m = eng.model
    return (m, eng.params,
            jax.jit(functools.partial(m.prefill, remat_policy="none",
                                      dtype=jnp.float32)),
            jax.jit(functools.partial(m.decode_step, dtype=jnp.float32)))


@functools.lru_cache(maxsize=None)
def _reference_stream(arch, prompt, n):
    """One request alone: an unpadded ``(1, lp)`` prefill on a fresh
    one-row cache with the canonical page table, then greedy
    ``decode_step`` until ``n`` tokens."""
    m, params, prefill, decode = _reference_programs(arch)
    cfg = m.cfg
    prompt = np.asarray(prompt, np.int32)
    caches = m.make_decode_caches(1, max_seq=MAX_SEQ, kv_dtype=jnp.float32)
    kv = caches.self_kv if cfg.is_encdec else caches.kv
    if kv is not None:
        kv = kv._replace(page_table=jnp.arange(
            kv.page_table.shape[1], dtype=jnp.int32)[None])
        caches = (caches._replace(self_kv=kv) if cfg.is_encdec
                  else caches._replace(kv=kv))
    batch = {"tokens": jnp.asarray(prompt[None])}
    if cfg.modality == "audio":
        batch["src_embeds"] = jnp.zeros((1, MAX_SEQ, cfg.d_model),
                                        jnp.float32)
        batch["src_valid"] = jnp.asarray([len(prompt)], jnp.int32)
    logits, caches = prefill(params, batch, caches)
    out = [int(jnp.argmax(logits[0]))]
    while len(out) < n:
        logits, caches = decode(params, jnp.asarray([[out[-1]]], jnp.int32),
                                caches)
        out.append(int(jnp.argmax(logits[0])))
    return out


def _rows_equal(before, after, keep, axis):
    """Rows ``keep`` along ``axis`` hold the same values (dtypes may
    promote, exactly, on a family's first admission)."""
    b = np.take(before, keep, axis=axis).astype(np.float64)
    a = np.take(after, keep, axis=axis).astype(np.float64)
    return np.array_equal(a, b, equal_nan=True)


def _check_isolated(eng, before, admitted):
    """Everything an admission into ``admitted`` must leave alone."""
    cfg, after = eng.cfg, eng.caches
    keep = [s for s in range(eng.max_batch) if s not in admitted]
    kv0 = before.self_kv if cfg.is_encdec else before.kv
    kv1 = after.self_kv if cfg.is_encdec else after.kv
    if kv1 is not None:
        pt = np.asarray(kv1.page_table)
        assert _rows_equal(kv0.page_table, pt, keep, 0)
        assert _rows_equal(kv0.seq_lens, kv1.seq_lens, keep, 0)
        for s in admitted:
            lp = len(eng.slot_req[s].prompt)
            assert int(kv1.seq_lens[s]) == lp
            assert (pt[s] >= 0).sum() == -(-(lp + 1) // eng.page)
        # heap words outside the admitted rows' pages are untouched
        theirs = np.concatenate([pt[s][pt[s] >= 0] for s in admitted])
        others = np.setdiff1d(np.arange(kv1.layers.k.shape[1]), theirs)
        for old, new in zip(jax.tree.leaves(kv0.layers),
                            jax.tree.leaves(kv1.layers)):
            assert _rows_equal(old, np.asarray(new), others, 1)
    if cfg.is_encdec:
        for name in ("cross_k", "cross_v"):
            assert _rows_equal(getattr(before, name),
                               np.asarray(getattr(after, name)), keep, 1)
        assert _rows_equal(before.enc_valid, after.enc_valid, keep, 0)
        for s in admitted:
            assert int(after.enc_valid[s]) == len(eng.slot_req[s].prompt)
    else:
        for name in ("ssm_h", "ssm_conv"):
            if getattr(after, name) is not None:
                assert _rows_equal(getattr(before, name),
                                   np.asarray(getattr(after, name)),
                                   keep, 1)


@pytest.mark.parametrize("mega", [False, True], ids=["host", "mega"])
@pytest.mark.parametrize("arch", FAMILIES)
def test_admission_prefills_one_row(arch, mega):
    """Five requests through three slots, so later ones land in slots
    freed at different ticks: each stream equals the request served
    alone by an unpadded prefill, and each admission leaves the other
    slots' caches as they were."""
    cfg, make = _factory(arch)
    eng = make(mega=mega)
    rng = np.random.default_rng(7)
    want = {}
    for _ in range(5):
        prompt = tuple(int(t) for t in rng.integers(
            2, cfg.vocab_size, int(rng.choice([7, 19]))))
        n = int(rng.integers(3, 9))
        want[eng.submit(prompt, max_new_tokens=n)] = (prompt, n)
    slots, done = set(), []
    for _ in range(200):
        if not eng.waiting and all(r is None for r in eng.slot_req):
            break
        before = jax.tree.map(np.array, eng.caches)
        free = [s for s, r in enumerate(eng.slot_req) if r is None]
        eng._admit()
        admitted = [s for s in free if eng.slot_req[s] is not None]
        if admitted:
            _check_isolated(eng, before, admitted)
            slots.update(admitted)
        done += eng.step()
    assert len(done) == 5 and slots == {0, 1, 2}
    assert eng.stats["frees"] == eng.stats["allocs"]
    for r in done:
        prompt, n = want[r.uid]
        assert r.out_tokens == _reference_stream(arch, prompt, n), r.uid


def test_prefill_program_is_shared_by_slots():
    """The slot is a traced argument: prompts of one length admitted
    into three slots compile one prefill program; a new length one
    more."""
    cfg, make = _factory("qwen2-0.5b")
    eng = make(mega=False)
    rng = np.random.default_rng(1)
    for _ in range(3):
        eng.submit(rng.integers(2, cfg.vocab_size, 12), max_new_tokens=2)
    n0 = eng._prefill._cache_size()
    eng._admit()
    assert all(r is not None for r in eng.slot_req)
    assert eng._prefill._cache_size() == n0 + 1
    eng.run_until_done(50)
    eng.submit(rng.integers(2, cfg.vocab_size, 20), max_new_tokens=2)
    eng._admit()
    assert eng._prefill._cache_size() == n0 + 2


def test_prefill_counters_span_and_metrics():
    """``prefill_rows`` counts admissions and ``prefill_tokens`` the
    prompt tokens (one row each); both survive snapshot/restore,
    publish as ``repro_engine_prefill_*_total``, and every ``prefill``
    span says ``rows=1``."""
    cfg, make = _factory("qwen2-0.5b")
    tracer = obs_trace.Tracer(enabled=True)
    eng = make(mega=True, tracer=tracer)
    rng = np.random.default_rng(2)
    lens = [5, 17, 9, 26]
    for lp in lens:
        eng.submit(rng.integers(2, cfg.vocab_size, lp), max_new_tokens=4)
    eng.step()                               # admits the first three
    assert eng.stats["prefill_rows"] == 3
    assert eng.stats["prefill_tokens"] == sum(lens[:3])
    snap = eng.snapshot()
    twin = make(mega=True)
    twin.restore(snap)
    for k in ("prefill_rows", "prefill_tokens"):
        assert twin.stats[k] == eng.stats[k]
    assert len(eng.run_until_done(100)) == 4
    assert eng.stats["prefill_rows"] == len(lens)
    assert eng.stats["prefill_tokens"] == sum(lens)
    reg = eng.publish_metrics()
    assert reg.get("repro_engine_prefill_rows_total").samples[()] == 4
    assert (reg.get("repro_engine_prefill_tokens_total").samples[()]
            == sum(lens))
    assert "repro_engine_prefill_tokens_total" in reg.to_prometheus()
    pre = [e["args"] for e in tracer.events if e["name"] == "prefill"]
    assert [a["rows"] for a in pre] == [1] * len(lens)
    assert sorted(a["prompt_len"] for a in pre) == sorted(lens)
