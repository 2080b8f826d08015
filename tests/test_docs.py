"""Documentation lockdown: public-API doctests + DESIGN.md drift.

Two failure modes this file exists to catch:

1. **Dead examples** — the docstring examples on the public API
   surface (``Ouroboros``, ``Arena``/``ArenaLayout``, ``ShardedArena``
   and friends, ``transactions.alloc/free``,
   ``kv_cache.make_kv_allocator``) are executable doctests; this
   suite runs them, so a signature or behaviour change that breaks an
   example fails CI (the docs job also runs them via
   ``pytest --doctest-modules``).

2. **Doc drift** — DESIGN.md §7–§9 embed offset/blocking tables that
   are RENDERED from the live layout (``ArenaLayout.describe()`` /
   ``ShardLayout.describe()`` / ``Region.blocking``).  test_heap.py
   pins §7; the checks here extend the same mechanism to §8's
   region-blocking table and §9's sharded tables, so none of the
   three sections can silently diverge from the code.
"""
import doctest
import importlib
import pathlib
import re

import pytest

from repro.core import HeapConfig, arena, shards

DOC = pathlib.Path(__file__).resolve().parent.parent / "DESIGN.md"
CFG = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
                 min_page_bytes=16)

# The documented public API surface.  Every module here must carry at
# least one runnable example — an empty doctest run means the usage
# examples were deleted, which is itself a docs regression.
DOCTEST_MODULES = (
    "repro.core.ouroboros",
    "repro.core.arena",
    "repro.core.defrag",
    "repro.core.shards",
    "repro.core.transactions",
    "repro.paged.kv_cache",
)


@pytest.mark.parametrize("modname", DOCTEST_MODULES)
def test_public_api_doctests(modname):
    mod = importlib.import_module(modname)
    res = doctest.testmod(mod, verbose=False)
    assert res.attempted > 0, (
        f"{modname} lost its runnable usage examples (no doctests "
        f"collected)")
    assert res.failed == 0, (
        f"{modname}: {res.failed}/{res.attempted} doctest examples "
        f"failed — run `pytest --doctest-modules src/{modname.replace('.', '/')}.py` "
        f"for details")


# ---- DESIGN.md §8: the region-blocking table ------------------------------

def test_design_s8_blocking_table_matches_live_policies():
    """Every (region, blocking) pair in the live layouts must appear
    on the §8 table row for that blocking class — so changing a
    ``Region.blocking`` without updating DESIGN.md §8 fails here."""
    doc = DOC.read_text()
    sec = doc.split("## §8")[1].split("\n## §")[0]
    rows = {}
    for m in re.finditer(r"\| `(window|untouched)`[^\n]*", sec):
        rows[m.group(1)] = m.group(0)
    live = {}
    for kind in arena.KINDS:
        for family in arena.QUEUE_FAMILIES:
            for r in arena.layout(CFG, kind, family).regions:
                live.setdefault(r.blocking, set()).add(r.name)
    assert set(live) <= set(rows), (
        f"DESIGN.md §8 table lost rows: {set(live) - set(rows)}")
    for blocking, names in live.items():
        for nm in sorted(names):
            assert f"`{nm}`" in rows[blocking], (
                f"DESIGN.md §8 drifted: region {nm!r} is "
                f"{blocking!r}-blocked in the live layout but absent "
                f"from that table row")


# ---- DESIGN.md §9: the sharded layout tables ------------------------------

def test_design_s9_shard_tables_match_live_layout():
    """§9's example tables are ``ShardLayout.describe()`` renderings;
    re-render and require the header/offset lines verbatim, exactly as
    test_heap.py pins §7 to ``ArenaLayout.describe()``."""
    doc = DOC.read_text()
    for kind, family in (("page", "ring"), ("chunk", "vl")):
        desc = shards.layout(CFG, 4, kind, family).describe()
        lines = [ln for ln in desc.splitlines()
                 if "mem[" in ln or ln.startswith("sharded arena(")
                 or "global heap offset" in ln]
        assert lines, "describe() rendering changed shape"
        for ln in lines:
            assert ln in doc, (
                f"DESIGN.md §9 drifted from the live sharded layout: "
                f"{ln!r}")


def test_design_s9_walk_schedule_documented():
    """The §9 schedule keywords the tests rely on stay documented."""
    sec = DOC.read_text().split("## §9")[1].split("\n## §")[0]
    for needle in ("attempt-major", "overflow walk", "shard_hint",
                   "ONE pallas_call", "serial replay"):
        assert needle in sec, f"DESIGN.md §9 lost {needle!r}"


# ---- DESIGN.md §10: the defragmentation contract --------------------------

def test_design_s10_defrag_documented():
    """The §10 contract keywords tests/test_defrag.py relies on stay
    documented: the plan/execute split, the forwarding-table format,
    the one-kernel waves, and the shard-rebalance policy."""
    sec = DOC.read_text().split("## §10")[1].split("\n## §")[0]
    for needle in ("plan/execute split", "Forwarding(src, dst, sizes)",
                   "ONE `pallas_call` per wave", "class-major rebuild",
                   "rebalance", "most-loaded", "least-loaded",
                   "apply_forwarding", "frag_ratio", "max_moves"):
        assert needle in sec, f"DESIGN.md §10 lost {needle!r}"


# ---- DESIGN.md §11: the fused decode mega-step ----------------------------

def test_design_s11_mega_step_documented():
    """The §11 contract keywords tests/test_serve_mega.py relies on
    stay documented: the five fused stages, the word-offset page
    table, the flag-vector host sync, and the launch-count proof."""
    sec = DOC.read_text().split("## §11")[1].split("\n## §")[0]
    for needle in ("mega_step=True", "Ouroboros.grow", "grow_lanes",
                   "scatter_grant_words", "donate_argnums",
                   "launches_per_tick", "flag vector",
                   "merge_rows", "BENCH_serve.json",
                   "count_pallas_calls", "wpp"):
        assert needle in sec, f"DESIGN.md §11 lost {needle!r}"


# ---- DESIGN.md §12: crash-safe serving ------------------------------------

def test_design_s12_crash_safe_serving_documented():
    """The §12 contract keywords tests/test_serve_snapshot.py and the
    CI crash-restart smoke rely on stay documented: what is
    snapshotted (array tree vs JSON sidecar), the fingerprint
    validation contract and its golden pin, the recompute-vs-reload
    split, the serve-driver wiring, and eviction degradation."""
    sec = DOC.read_text().split("## §12")[1].split("\n## §")[0]
    for needle in ("snapshot()", "restore()", "snapshot_fingerprint",
                   "describe()", "meta.json", "extra",
                   "serve_snapshot_fingerprint.txt", "donate_argnums",
                   "PreemptionGuard", "--snapshot-dir", "--resume",
                   "REQ <uid>", "evictions", "youngest",
                   "refresh_frag_stats", "exit"):
        assert needle in sec, f"DESIGN.md §12 lost {needle!r}"


# ---- DESIGN.md §13: the traffic-replay harness ----------------------------

def test_design_s13_replay_documented():
    """The §13 contract keywords tests/test_replay.py and the fig9
    benchmark rely on stay documented: the traffic model, the
    per-modality page policy, the cancellation states, the parity the
    harness asserts, and the conservation invariant."""
    sec = DOC.read_text().split("## §13")[1].split("\n## §")[0]
    for needle in ("generate_trace", "Poisson", "burst", "abandon",
                   "cancel(uid)", "waiting", "retired",
                   "modality_page_quota", "aux", "replay_pair",
                   "token-for-token", "allocs == frees",
                   "assert_conserved", "p50", "p99",
                   "BENCH_serve.json", "fig9_replay"):
        assert needle in sec, f"DESIGN.md §13 lost {needle!r}"


def test_design_s13_pins_serve_record_schema():
    """§13 documents the BENCH_serve.json record schema; the live
    schema constants must appear there verbatim so the validator and
    the doc cannot drift apart."""
    from benchmarks.common import (REPLAY_CELL_KEYS, SERVE_RECORD_KEYS,
                                   SERVE_RECORD_KINDS)

    sec = DOC.read_text().split("## §13")[1].split("\n## §")[0]
    for kind in SERVE_RECORD_KINDS:
        assert f'"{kind}"' in sec, (
            f"DESIGN.md §13 lost record kind {kind!r}")
    for key in SERVE_RECORD_KEYS:
        assert f"`{key}`" in sec, (
            f"DESIGN.md §13 lost envelope key {key!r}")
    for key in REPLAY_CELL_KEYS:
        assert f"`{key}`" in sec, (
            f"DESIGN.md §13 lost replay telemetry key {key!r}")


# ---- DESIGN.md §14: the observability layer --------------------------------

def test_design_s14_telemetry_word_table_matches_live_layout():
    """§14's telemetry word table is a ``describe()`` rendering for
    the §7 test config; re-render and require every telemetry ctl
    line verbatim, so the documented offsets track
    ``ArenaLayout.tele_fields()`` exactly."""
    sec = DOC.read_text().split("## §14")[1].split("\n## §")[0]
    lay = arena.layout(CFG, "page", "ring")
    tele_lines = [ln for ln in lay.describe().splitlines()
                  if any(f"  {name}" in ln
                         for name, _, _ in lay.tele_fields())]
    assert len(tele_lines) == len(lay.tele_fields())
    for ln in tele_lines:
        assert ln in sec, (
            f"DESIGN.md §14 drifted from the live telemetry layout: "
            f"{ln!r}")
    # every field is prose-documented too
    for name, _, _ in lay.tele_fields():
        assert f"`{name}" in sec, f"DESIGN.md §14 lost field {name!r}"


def test_design_s14_span_taxonomy_and_metric_names_documented():
    """The §14 span taxonomy must list ``trace.PHASES`` verbatim and
    the metric family names the engine publishes must appear, so
    dashboards built from the doc match the live exposition."""
    from repro.obs.trace import PHASES

    sec = DOC.read_text().split("## §14")[1].split("\n## §")[0]
    for phase in PHASES:
        assert f'"{phase}"' in sec, f"DESIGN.md §14 lost span {phase!r}"
    for fam in ("repro_alloc_granted_total", "repro_free_total",
                "repro_alloc_failed_total", "repro_ring_wrap_total",
                "repro_segment_grow_total", "repro_segment_shrink_total",
                "repro_pool_wrap_total",
                "repro_overflow_walk_served_total",
                "repro_arena_frag_ratio", "repro_step_time_ms",
                "repro_engine_prefill_rows_total",
                "repro_engine_prefill_tokens_total"):
        assert fam in sec, f"DESIGN.md §14 lost metric family {fam!r}"
    for needle in ("validate_exposition", "require_phases=True",
                   "--metrics-file", "--trace-file", "obs_dump",
                   "jit_first_calls", "drain_telemetry",
                   "publish_metrics"):
        assert needle in sec, f"DESIGN.md §14 lost {needle!r}"
