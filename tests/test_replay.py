"""Traffic-replay harness tests (serve/replay.py, DESIGN.md §13).

The replay subsystem doubles as the serving engine's hardest
correctness net, so this file is where the zoo-wide guarantees live:

- seeded traces are **deterministic** (same seed → identical trace,
  different seed → different stream) and respect the engine's bounds;
- **cancellation** (the client-abandonment path) handles all three
  uid states — waiting, active, retired — and frees every page the
  request ever held (KV + modality aux) back through the allocator;
- **parity**: every stream a replay completes equals its request
  served alone (``serve_ref``), including under bursty load and
  mid-stream abandonment, and one and four allocator shards agree
  token-for-token, with end-state conservation;
- **no family untested**: a replay smoke runs over every arch in the
  zoo (tiny geometries), exercising the per-modality page policy —
  recurrent-state pages through the same arena as KV.

Marker ``replay`` (conftest.py): the forced-blocked CI job runs
``pytest -m replay``; the nightly job adds the two-scenario benchmark
smoke (``benchmarks/run.py --quick --fig fig9_replay``).
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import ALL_ARCHS
from repro.serve.replay import (SCENARIOS, Scenario, engine_factory,
                                assert_conserved, generate_trace,
                                replay, replay_pair)
from serve_ref import expected_stream

pytestmark = pytest.mark.replay


# ---- trace generation -----------------------------------------------------

def test_trace_determinism():
    """Same (scenario, seed) → identical trace; different seed →
    different stream; every scenario in the zoo is covered."""
    for name, sc in SCENARIOS.items():
        a = generate_trace(sc, seed=13, vocab_size=128)
        b = generate_trace(sc, seed=13, vocab_size=128)
        assert a == b, f"scenario {name} not deterministic"
        c = generate_trace(sc, seed=14, vocab_size=128)
        assert a != c, f"scenario {name} ignores its seed"


def test_trace_respects_engine_bounds():
    sc = SCENARIOS["bursty"]
    items = generate_trace(sc, seed=0, vocab_size=64, max_seq=48,
                           max_new_cap=8)
    assert len(items) == sc.n_requests
    assert items == sorted(items, key=lambda it: it.step)
    for it in items:
        assert 1 <= len(it.prompt) and it.max_new >= 1
        assert it.max_new <= 8
        assert len(it.prompt) + it.max_new <= 48
        assert all(2 <= t < 64 for t in it.prompt)


def test_abandon_scenario_schedules_cancels():
    items = generate_trace(SCENARIOS["abandon"], seed=1, vocab_size=64)
    cancels = [it for it in items if it.cancel_step is not None]
    assert cancels, "abandon scenario generated no abandonments"
    for it in cancels:
        assert it.cancel_step >= it.step


def test_scenario_validation():
    with pytest.raises(ValueError, match="arrival"):
        Scenario("bad", arrival="uniform")
    with pytest.raises(ValueError, match="abandon_frac"):
        Scenario("bad", abandon_frac=1.5)


# ---- cancellation: the client-abandonment engine path ---------------------

def _mini_trace(vocab, n=3, max_new=4):
    rng = np.random.default_rng(0)
    return [rng.integers(2, vocab, 6) for _ in range(n)], max_new


def test_cancel_waiting_active_retired():
    """Regression for the three uid states: a uid still in the waiting
    queue is removed before touching a slot; an active uid frees its
    pages; a retired (or never-submitted) uid is a no-op returning
    False — never a KeyError."""
    cfg, make = engine_factory("qwen2-0.5b", max_batch=2)
    eng = make()
    prompts, max_new = _mini_trace(cfg.vocab_size, n=4)
    uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]

    # 2 slots: after one step uids[0:2] are active, uids[2:] wait
    eng.step()
    active = {r.uid for r in eng.slot_req if r is not None}
    waiting = [r.uid for r in eng.waiting]
    assert len(active) == 2 and len(waiting) == 2

    assert eng.cancel(waiting[0]) is True          # waiting-queue path
    assert waiting[0] not in [r.uid for r in eng.waiting]
    live_before = eng.stats["allocs"] - eng.stats["frees"]
    victim = sorted(active)[0]
    assert eng.cancel(victim) is True              # active-slot path
    live_after = eng.stats["allocs"] - eng.stats["frees"]
    assert live_after < live_before, "cancel freed no pages"
    assert victim not in {r.uid for r in eng.slot_req if r is not None}
    assert eng.cancel(victim) is False             # already cancelled
    assert eng.cancel(10_000) is False             # never submitted

    done = eng.run_until_done(500)
    retired = done[0].uid
    assert eng.cancel(retired) is False            # retired: no-op
    assert {r.uid for r in done} == set(uids) - {waiting[0], victim}
    assert_conserved(eng)
    assert eng.stats["cancels"] == 2


@pytest.mark.parametrize("shards", [1, 4], ids=["shards1", "shards4"])
def test_abandonment_frees_all_pages(shards):
    """The headline conservation property: after an abandonment-heavy
    replay drains, every page ever granted — KV and modality aux alike
    — went back through the allocator (allocs == frees), no slot holds
    pages, and the device page table is all holes."""
    cfg, make = engine_factory("granite-4.0-h-small")   # aux pages > 0
    eng = make(num_shards=shards)
    assert eng.aux_pages > 0, "a paged-state config carries state pages"
    trace = generate_trace(SCENARIOS["abandon"], seed=5,
                           vocab_size=cfg.vocab_size)
    r = replay(eng, trace, scenario="abandon")
    assert r.cancelled, "abandon trace cancelled nothing"
    assert_conserved(eng)
    assert eng.stats["cancels"] == len(r.cancelled)


def _assert_served_alone(arch, trace, result):
    """Every completed stream equals its request served alone (uids
    follow arrival order)."""
    items = sorted(trace, key=lambda it: it.step)
    assert result.tokens
    for uid, toks in result.tokens.items():
        it = items[uid - 1]
        assert toks == expected_stream(arch, it.prompt, it.max_new), uid


def test_bursty_parity_mega_vs_host():
    """Under a bursty trace that overruns max_batch (so the waiting
    queue and the allocator churn together), every stream equals its
    request served alone."""
    cfg, make = engine_factory("qwen2-0.5b")
    trace = generate_trace(SCENARIOS["bursty"], seed=11,
                           vocab_size=cfg.vocab_size)
    assert len(trace) > 3 * 2, "burst should overrun the batch"
    eng = make()
    r = replay(eng, trace, scenario="bursty")
    assert max(r.queue_wait.values()) > 0, "nothing ever queued"
    _assert_served_alone("qwen2-0.5b", trace, r)
    assert_conserved(eng)


def test_abandon_parity_with_aux_pages():
    """Through mid-stream cancels on a config whose slots hold state
    pages (the Mamba-2 state of a layer-list model), every completed
    stream equals its request served alone."""
    cfg, make = engine_factory("granite-4.0-h-small")
    eng = make()
    assert eng.aux_pages > 0
    trace = generate_trace(SCENARIOS["abandon"], seed=7,
                           vocab_size=cfg.vocab_size)
    r = replay(eng, trace, scenario="abandon")
    assert r.cancelled
    _assert_served_alone("granite-4.0-h-small", trace, r)
    assert_conserved(eng)


def test_shard_parity():
    """Shards 1 vs 4 on the same trace must agree token-for-token
    (hashed home-shard routing is an allocator-internal concern —
    DESIGN.md §9)."""
    cfg, make = engine_factory("qwen2-0.5b")
    trace = generate_trace(SCENARIOS["steady"], seed=2,
                           vocab_size=cfg.vocab_size)
    one, four = replay_pair(make(num_shards=1), make(num_shards=4),
                            trace, scenario="steady")
    assert one.tokens == four.tokens and one.tokens


# ---- the zoo: no model family untested ------------------------------------

_SMOKE = dataclasses.replace(SCENARIOS["abandon"], n_requests=4,
                             out_lens=(2, 5), abandon_frac=0.4)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_replay_smoke_every_config(arch):
    """Every arch in the zoo replays a short abandonment trace through
    the tick with conservation asserted — state pages and KV pages all
    route through the same Ouroboros arena, and no family is ever
    untested again."""
    cfg, make = engine_factory(arch, max_batch=2)
    eng = make()
    trace = generate_trace(_SMOKE, seed=3, vocab_size=cfg.vocab_size)
    r = replay(eng, trace, scenario="smoke")
    assert len(r.tokens) + len(r.cancelled) == len(trace)
    assert all(ts for ts in r.tokens.values())
    assert_conserved(eng)


def test_modality_page_quota_families():
    """The quota helper behind the aux policy counts the pages that hold
    state: a layer-list model's Mamba state pages, exactly; zero for
    every family whose per-sequence state is KV pages alone, dense
    per-slot arrays (mamba2, recurrentgemma), or none (an MoE layer
    keeps no per-sequence state)."""
    from repro.configs import get_arch
    from repro.paged.kv_cache import modality_page_quota

    quota = {a: modality_page_quota(get_arch(a).smoke())
             for a in ALL_ARCHS}
    assert quota["qwen2-0.5b"] == 0 and quota["qwen2-vl-2b"] == 0
    assert quota["seamless-m4t-large-v2"] == 0
    assert quota["mamba2-780m"] == 0 and quota["recurrentgemma-9b"] == 0
    assert quota["mixtral-8x7b"] == 0 and quota["phi3.5-moe-42b-a6.6b"] == 0
    assert quota["granite-4.0-h-small"] > 0
    # exactness: a page row is a page id's K and V slots over the
    # attention layers (float32); each Mamba layer takes its SSD heads
    # whole rows at a time, plus its conv tail
    cfg = get_arch("granite-4.0-h-small").smoke()
    row = 2 * 1 * 16 * cfg.num_kv_heads * cfg.head_dim_
    heads = cfg.ssm_nheads * cfg.ssm_headdim * cfg.ssm_state
    tail = (cfg.ssm_conv - 1) * (cfg.d_inner + 2 * cfg.ssm_state)
    assert quota["granite-4.0-h-small"] == 2 * (heads // row
                                                + -(-tail // row))
    # at published widths: 9 Mamba layers × (32 pages of 4 heads + 1)
    assert modality_page_quota(
        get_arch("granite-4.0-h-small.stage0")) == 9 * (32 + 1)


# ---- telemetry + BENCH_serve.json schema ----------------------------------

def test_replay_summary_is_schema_complete():
    """A ReplayResult.summary() cell carries every telemetry key the
    BENCH_serve.json replay schema requires — the benchmark can never
    append a record the validator rejects."""
    from benchmarks.common import REPLAY_CELL_KEYS

    cfg, make = engine_factory("qwen2-0.5b")
    trace = generate_trace(SCENARIOS["steady"], seed=0,
                           vocab_size=cfg.vocab_size)
    s = replay(make(), trace, scenario="steady").summary()
    assert all(k in s for k in REPLAY_CELL_KEYS)
    assert s["tick_ms_p99"] >= s["tick_ms_p50"] >= 0.0
    assert s["queue_wait_p99"] >= s["queue_wait_p50"] >= 0.0
    assert s["completed"] + s["cancelled"] == s["requests"]


def _replay_cell():
    from benchmarks.common import REPLAY_CELL_KEYS
    return {k: 0 for k in REPLAY_CELL_KEYS}


def test_validate_serve_record():
    """The benchmarks/common.py schema validator: legacy records
    (no ``record`` key) pass as kind "serve"; replay records need the
    full telemetry cell; every violation raises with the offending
    key named."""
    from benchmarks.common import validate_serve_record as v

    legacy = {"platform": "cpu", "git_sha": "abc", "quick": True,
              "cells": {"host/jnp": {"tokens": 1}}}
    assert v(legacy) == "serve"
    assert v(dict(legacy, record="serve")) == "serve"
    assert v(dict(legacy, record="replay",
                  cells={"a/b/c/host": _replay_cell()})) == "replay"

    with pytest.raises(ValueError, match="kind"):
        v(dict(legacy, record="perf"))
    with pytest.raises(ValueError, match="git_sha"):
        v({"platform": "cpu", "cells": {"x": {}}})
    with pytest.raises(ValueError, match="cells"):
        v(dict(legacy, cells={}))
    with pytest.raises(ValueError, match="tick_ms_p99"):
        bad = _replay_cell()
        del bad["tick_ms_p99"]
        v(dict(legacy, record="replay", cells={"a": bad}))
    with pytest.raises(ValueError, match="dict"):
        v(["not", "a", "record"])


def test_bench_serve_json_is_schema_valid():
    """Every record already in the repo's BENCH_serve.json trajectory
    validates — the append-only file can never accumulate a record the
    schema helpers would reject."""
    import pathlib

    from benchmarks.common import load_runs, validate_serve_record

    path = pathlib.Path(__file__).resolve().parent.parent / \
        "BENCH_serve.json"
    runs = load_runs(str(path))
    assert runs, "BENCH_serve.json lost its trajectory"
    kinds = [validate_serve_record(r) for r in runs]
    assert kinds[0] == "serve"      # the original fig8 record survives


def test_append_serve_record_is_append_only(tmp_path):
    """append_serve_record validates before writing, keeps prior runs,
    and refuses to touch an unparseable trajectory file."""
    from benchmarks.common import append_serve_record, load_runs

    p = str(tmp_path / "BENCH_serve.json")
    rec = {"platform": "cpu", "git_sha": "abc", "quick": True,
           "record": "replay",
           "cells": {"dense/q/steady/host": _replay_cell()}}
    assert append_serve_record(p, rec) == 1
    assert append_serve_record(p, rec) == 2
    assert [r["record"] for r in load_runs(p)] == ["replay", "replay"]

    with pytest.raises(ValueError):              # invalid: not written
        append_serve_record(p, {"platform": "cpu"})
    assert len(load_runs(p)) == 2

    with open(p, "w") as f:
        f.write("{corrupt")
    with pytest.raises(SystemExit, match="refusing"):
        append_serve_record(p, rec)
