"""Crash-safe serving tests (DESIGN.md §12).

The contract under test: ``ServingEngine.snapshot()/restore()``
captures the COMPLETE serving state — arena word image + control block
(all shards), KV page heaps + page tables + seq_lens, the tick's
carry + host mirrors, the request queue, and the stats block — such
that a restored engine (a) holds word-for-word identical arena/KV
state and (b) resumes decoding token-identically, across allocator
backends, lowerings, and shard counts.  A snapshot from a different
``ArenaLayout`` or engine geometry must be rejected loudly (the
fingerprint is pinned to ``tests/golden/``), never silently
misinterpreted.
"""
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.models.model import build_model

pytestmark = pytest.mark.ft

GOLDEN = pathlib.Path(__file__).resolve().parent / "golden"


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_arch("qwen2-0.5b").smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _engine(tiny_model, **kw):
    from repro.serve.engine import ServingEngine
    cfg, m, params = tiny_model
    kw.setdefault("kv_dtype", jnp.float32)
    kw.setdefault("compute_dtype", jnp.float32)
    return ServingEngine(m, params, max_batch=3, max_seq=96, **kw)


def _submit(eng, cfg, n=4, max_new=5, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        eng.submit(rng.integers(2, cfg.vocab_size,
                                int(rng.integers(4, 30))),
                   max_new_tokens=max_new)


def _toks(reqs):
    return {r.uid: list(r.out_tokens) for r in reqs}


# ---- word-for-word round-trip across the backend matrix -------------------

@pytest.mark.parametrize("backend,lowering,shards", [
    ("jnp", "auto", 1),
    ("jnp", "auto", 4),
    ("pallas", "whole", 1),
    ("pallas", "blocked", 1),
    ("pallas", "whole", 4),
    ("pallas", "blocked", 4),
])
def test_snapshot_roundtrip_word_for_word(tiny_model, backend,
                                          lowering, shards):
    """Snapshot mid-decode, restore into a FRESH engine: every arena
    word, control word, KV heap word, and page-table entry must match
    the source engine exactly — and the restored engine must finish
    the in-flight streams token-identically to an uninterrupted run."""
    cfg = tiny_model[0]
    kw = dict(alloc_backend=backend, alloc_lowering=lowering,
              num_shards=shards)

    ref = _engine(tiny_model, **kw)
    _submit(ref, cfg)
    want = _toks(ref.run_until_done(300))

    src = _engine(tiny_model, **kw)
    _submit(src, cfg)
    early = []
    for _ in range(3):
        early.extend(src.step())
    snap = src.snapshot()

    dst = _engine(tiny_model, **kw)
    assert dst.restore(snap) is None
    np.testing.assert_array_equal(np.asarray(src.alloc_state.mem),
                                  np.asarray(dst.alloc_state.mem))
    np.testing.assert_array_equal(np.asarray(src.alloc_state.ctl),
                                  np.asarray(dst.alloc_state.ctl))
    for a, b in zip(jax.tree.leaves(src.caches),
                    jax.tree.leaves(dst.caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    got = _toks(early + dst.run_until_done(300))
    assert got == want
    assert dst.stats["frees"] == dst.stats["allocs"]


def test_snapshot_restores_across_backend_and_lowering(tiny_model):
    """Backend/lowering are deliberately NOT in the fingerprint:
    transactions are bit-identical across them, so a snapshot taken on
    the jnp reference path restores onto fused Pallas kernels (and the
    blocked lowering) mid-stream with identical output."""
    cfg = tiny_model[0]
    ref = _engine(tiny_model, alloc_backend="jnp")
    _submit(ref, cfg)
    want = _toks(ref.run_until_done(300))

    src = _engine(tiny_model, alloc_backend="jnp")
    _submit(src, cfg)
    early = []
    for _ in range(3):
        early.extend(src.step())
    snap = src.snapshot()

    dst = _engine(tiny_model, alloc_backend="pallas",
                  alloc_lowering="blocked")
    dst.restore(snap)
    assert _toks(early + dst.run_until_done(300)) == want


# ---- kill-mid-decode → restore → token parity (tmp_path = "disk") ---------

@pytest.mark.parametrize("shards", [1, 4], ids=["shards1", "shards4"])
def test_kill_mid_decode_restores_token_identically(tiny_model, shards,
                                                    tmp_path):
    """The crash path: decode a few ticks, snapshot to a committed
    on-disk checkpoint, DISCARD the engine (the "kill"), restore in a
    fresh process-equivalent engine, finish — killed-run + resumed-run
    streams concatenate to exactly the uninterrupted run's streams,
    on one allocator shard and on four."""
    cfg = tiny_model[0]
    ref = _engine(tiny_model, num_shards=shards)
    _submit(ref, cfg)
    want = _toks(ref.run_until_done(300))

    eng = _engine(tiny_model, num_shards=shards)
    _submit(eng, cfg)
    early = []
    for _ in range(4):
        early.extend(eng.step())
    eng.snapshot(directory=str(tmp_path))
    del eng  # the crash

    resumed = _engine(tiny_model, num_shards=shards)
    step = resumed.restore(str(tmp_path))
    assert step == 4
    got = _toks(early + resumed.run_until_done(300))
    assert got == want
    assert resumed.stats["frees"] == resumed.stats["allocs"]


# ---- layout-validation contract (golden pin + loud rejection) -------------

def test_snapshot_fingerprint_matches_golden(tiny_model):
    """The fingerprint of the canonical test engine is pinned to
    tests/golden/ — any change to the arena layout rendering, the
    allocator geometry, or the fingerprinted engine fields shows up as
    a reviewable golden diff (and invalidates old snapshots loudly)."""
    eng = _engine(tiny_model)
    got = json.dumps(eng.snapshot_fingerprint(), indent=2,
                     sort_keys=True) + "\n"
    want = (GOLDEN / "serve_snapshot_fingerprint.txt").read_text()
    assert got == want, (
        "serving snapshot fingerprint drifted from "
        "tests/golden/serve_snapshot_fingerprint.txt — if the layout "
        "change is intentional, re-render the golden and note that "
        "existing snapshots are invalidated")


def test_restore_rejects_mismatched_layout(tiny_model):
    """A snapshot whose fingerprint differs — different shard count,
    or a tampered arena-layout rendering — is rejected with a
    ValueError naming the differing fields BEFORE any engine state is
    mutated."""
    cfg = tiny_model[0]
    src = _engine(tiny_model, num_shards=1)
    _submit(src, cfg)
    src.step()
    snap = src.snapshot()

    other = _engine(tiny_model, num_shards=4)
    ctl_before = np.asarray(other.alloc_state.ctl).copy()
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        other.restore(snap)
    np.testing.assert_array_equal(np.asarray(other.alloc_state.ctl),
                                  ctl_before)

    tampered = {"tree": snap["tree"],
                "meta": json.loads(json.dumps(snap["meta"]))}
    tampered["meta"]["fingerprint"]["arena_layout"] += " (tampered)"
    dst = _engine(tiny_model, num_shards=1)
    with pytest.raises(ValueError, match="arena_layout"):
        dst.restore(tampered)


def test_restore_rejects_version_1_snapshot(tiny_model):
    """A version-1 snapshot was taken under another page-holder rule
    (host page lists beside the page table, and a host decode loop):
    it is refused loudly, naming the version, before any engine state
    is mutated."""
    cfg = tiny_model[0]
    src = _engine(tiny_model)
    _submit(src, cfg)
    src.step()
    snap = src.snapshot()
    old = {"tree": snap["tree"],
           "meta": json.loads(json.dumps(snap["meta"]))}
    fp = old["meta"]["fingerprint"]
    fp.update(snapshot_version=1, mega_step=True)
    old["meta"]["slot_pages"] = [[] for _ in range(3)]
    dst = _engine(tiny_model)
    ctl_before = np.asarray(dst.alloc_state.ctl).copy()
    with pytest.raises(ValueError, match="snapshot_version"):
        dst.restore(old)
    np.testing.assert_array_equal(np.asarray(dst.alloc_state.ctl),
                                  ctl_before)
    assert dst.waiting == [] and dst.stats["steps"] == 0


def test_restore_rejects_non_snapshot_checkpoint(tiny_model, tmp_path):
    """A plain training checkpoint (no fingerprint sidecar) under the
    snapshot dir is refused, not misread."""
    from repro.ckpt import checkpoint as CK
    CK.save({"w": jnp.zeros(4)}, str(tmp_path), step=1)
    eng = _engine(tiny_model)
    with pytest.raises(ValueError, match="not a serving-engine"):
        eng.restore(str(tmp_path))


# ---- eviction degradation surfaces in the snapshot state ------------------

def test_snapshot_carries_queue_and_eviction_stats(tiny_model):
    """The JSON sidecar round-trips the waiting queue, in-flight
    requests, and counters — including ``evictions`` — so a restored
    engine's stats are continuous with the killed run's."""
    cfg = tiny_model[0]
    eng = _engine(tiny_model)
    _submit(eng, cfg, n=6)  # 6 requests > 3 slots → some stay queued
    eng.step()
    eng.stats["evictions"] = 2  # pretend the killed run degraded
    snap = eng.snapshot()

    dst = _engine(tiny_model)
    dst.restore(snap)
    assert dst.stats["evictions"] == 2
    assert len(dst.waiting) == len(eng.waiting)
    assert [r and r.uid for r in dst.slot_req] == \
        [r and r.uid for r in eng.slot_req]
    got = _toks(dst.run_until_done(300))
    assert sorted(got) == list(range(1, 7))


# ---- observability state rides in the snapshot (DESIGN.md §14) ------------

@pytest.mark.obs
def test_snapshot_roundtrips_telemetry_and_metrics(tiny_model):
    """The in-kernel telemetry words are ordinary ctl words, so a
    restored engine drains word-identical accumulators — and a metrics
    registry publishing from the restored engine reports the same
    counter totals as one publishing from the source.  The telemetry
    is non-trivial by the time we snapshot (allocs have happened), so
    this is not an all-zeros comparison."""
    cfg = tiny_model[0]
    src = _engine(tiny_model)
    _submit(src, cfg)
    for _ in range(3):
        src.step()
    tele_src = src.drain_telemetry()
    assert int(np.asarray(tele_src["t_alloc"]).sum()) > 0
    snap = src.snapshot()

    dst = _engine(tiny_model)
    dst.restore(snap)
    tele_dst = dst.drain_telemetry()
    assert sorted(tele_src) == sorted(tele_dst)
    for field in tele_src:
        np.testing.assert_array_equal(
            np.asarray(tele_src[field]), np.asarray(tele_dst[field]),
            err_msg=f"telemetry {field} not restored word-for-word")

    from repro.obs.metrics import MetricsRegistry, validate_exposition
    text_src = src.publish_metrics(MetricsRegistry()).to_prometheus()
    text_dst = dst.publish_metrics(MetricsRegistry()).to_prometheus()
    validate_exposition(text_src)

    def totals(text, keep):
        return sorted(l for l in text.splitlines()
                      if l.startswith(keep))
    for fam in ("repro_alloc_granted_total", "repro_free_total",
                "repro_alloc_failed_total", "repro_engine_allocs_total",
                "repro_engine_steps_total"):
        assert totals(text_src, fam) == totals(text_dst, fam), (
            f"{fam} diverged across snapshot/restore")

    # the restored stream continues token-identically with telemetry
    # still accumulating monotonically
    dst.run_until_done(300)
    tele_after = dst.drain_telemetry()
    assert int(np.asarray(tele_after["t_alloc"]).sum()) >= \
        int(np.asarray(tele_dst["t_alloc"]).sum())
