"""Admission prefill tests (serve/engine.py ``_admit``, ``put_row``).

The contract under test: admitting a request computes ONE row — a
``(1, lp)`` prefill through a view of the caches that holds the shared
page heap and the admitted slot's page-table row — and writes that row
back at the slot, which the prefill program takes as a traced argument.
So every family's streams equal a standalone unpadded prefill of the
prompt followed by greedy decode (``serve_ref``), in any slot and on
one or four allocator shards; an admission leaves every other slot's
heap words, page-table row, ``seq_lens`` and state rows as they were;
one program serves every slot of a prompt length; and the engine
counts the rows and token-rows its prefills computed.

Everything runs float32 (kv + compute): greedy streams must be exact.
"""
import numpy as np
import pytest

import jax

from repro.obs import trace as obs_trace
from serve_ref import factory as _factory
from serve_ref import reference_stream

pytestmark = pytest.mark.serve

FAMILIES = ["qwen2-0.5b", "mixtral-8x7b", "mamba2-780m",
            "recurrentgemma-9b", "seamless-m4t-large-v2"]


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


@pytest.mark.parametrize("shards", [1, 4], ids=["shards1", "shards4"])
@pytest.mark.parametrize("arch", FAMILIES)
def test_admission_prefills_one_row(arch, shards):
    """Five requests through three slots, so later ones land in slots
    freed at different ticks: each stream equals the request served
    alone by an unpadded prefill, and each admission leaves the other
    slots' caches as they were."""
    cfg, make = _factory(arch)
    eng = make(num_shards=shards)
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
        assert r.out_tokens == reference_stream(arch, prompt, n), r.uid


def test_prefill_program_is_shared_by_slots():
    """The slot is a traced argument: prompts of one length admitted
    into three slots compile one prefill program; a new length one
    more."""
    cfg, make = _factory("qwen2-0.5b")
    eng = make()
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
    eng = make(tracer=tracer)
    rng = np.random.default_rng(2)
    lens = [5, 17, 9, 26]
    for lp in lens:
        eng.submit(rng.integers(2, cfg.vocab_size, lp), max_new_tokens=4)
    eng.step()                               # admits the first three
    assert eng.stats["prefill_rows"] == 3
    assert eng.stats["prefill_tokens"] == sum(lens[:3])
    snap = eng.snapshot()
    twin = make()
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
