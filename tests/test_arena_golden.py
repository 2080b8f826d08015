"""Golden snapshot of the arena word layout AND the per-region blocked
-lowering treatment.

DESIGN.md §7 documents the offset map and §8 the per-region treatment;
both are rendered from the live ``ArenaLayout`` (test_heap.py pins §7
prose to ``describe()``).  This test goes one step further and pins
the full rendering — offsets, shapes, blocking policy, and SMEM window
per region, for all six variants — to a checked-in golden file, so ANY
layout drift (a reordered region, a changed window, a region silently
dropped from the kernel) fails loudly instead of silently breaking
cross-lowering parity or corrupting live heaps on a version upgrade.

To regenerate after an *intentional* layout change:

    PYTHONPATH=src python -c "
    from repro.core import HeapConfig, arena
    cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
                     min_page_bytes=16)
    print('\\n'.join(arena.layout(cfg, k, f).describe(blocks=True)
                     for k in arena.KINDS
                     for f in arena.QUEUE_FAMILIES))
    " > tests/golden/arena_layout.txt

and justify the diff in the PR.
"""
import pathlib

import pytest

from repro.core import HeapConfig
from repro.core import arena

CFG = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
                 min_page_bytes=16)
GOLDEN = pathlib.Path(__file__).parent / "golden" / "arena_layout.txt"


def _render() -> str:
    return "\n".join(arena.layout(CFG, kind, family).describe(blocks=True)
                     for kind in arena.KINDS
                     for family in arena.QUEUE_FAMILIES) + "\n"


def test_layout_and_block_shapes_match_golden():
    want = GOLDEN.read_text()
    got = _render()
    assert got == want, (
        "arena layout or region block shapes drifted from the golden "
        "snapshot (tests/golden/arena_layout.txt).  If the change is "
        "intentional, regenerate the golden file (see module "
        "docstring) and call the drift out in the PR — live arenas "
        "serialized under the old layout will NOT survive it.")


@pytest.mark.parametrize("kind", arena.KINDS)
@pytest.mark.parametrize("family", arena.QUEUE_FAMILIES)
def test_block_shapes_consistent_with_policy(kind, family):
    """Structural invariants the blocked lowering relies on, config-
    independent: every region the kernel reaches is staged through one
    tile-aligned SMEM window, and untouched regions are exactly the
    ones the transactions never write."""
    lay = arena.layout(CFG, kind, family)
    for r in lay.regions:
        assert r.blocking in ("window", "untouched")
        if r.blocking == "window":
            assert r.block_shape == (arena.WINDOW_WORDS,)
        else:
            assert r.block_shape is None
    # the heap is written only by segment traffic; the pool only ever
    # moves for virtualized queues or chunk claims
    assert (lay.region("heap").blocking == "untouched") == \
        (family == "ring")
    assert (lay.region("pool_store").blocking == "untouched") == \
        (family == "ring" and kind == "page")


def test_split_join_roundtrip():
    """split/join (the blocked wrapper's mem plumbing) is lossless."""
    import jax.numpy as jnp
    lay = arena.layout(CFG, "chunk", "vl")
    mem = jnp.arange(lay.mem_words, dtype=jnp.int32)
    parts = arena.split(lay, mem)
    assert set(parts) == {r.name for r in lay.regions}
    assert (arena.join(lay, parts) == mem).all()


# ---- sharded layout golden (DESIGN.md §9) ---------------------------------

from repro.core import shards

SHARD_GOLDEN = pathlib.Path(__file__).parent / "golden" / "shard_layout.txt"
SHARDS = 4


def _render_sharded() -> str:
    return "\n".join(
        shards.layout(CFG, SHARDS, kind, family).describe(blocks=True)
        for kind in arena.KINDS for family in arena.QUEUE_FAMILIES) + "\n"


def test_shard_layout_matches_golden():
    """The sharded layout rendering — per-shard word table, global
    offset rule, routing line — is pinned like the single-arena one.
    Regenerate intentionally with:

        PYTHONPATH=src python -c "
        from repro.core import HeapConfig, shards, arena
        cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
                         min_page_bytes=16)
        print('\\n'.join(shards.layout(cfg, 4, k, f).describe(blocks=True)
                         for k in arena.KINDS
                         for f in arena.QUEUE_FAMILIES))
        " > tests/golden/shard_layout.txt
    """
    want = SHARD_GOLDEN.read_text()
    got = _render_sharded()
    assert got == want, (
        "sharded arena layout drifted from the golden snapshot "
        "(tests/golden/shard_layout.txt).  If intentional, regenerate "
        "(see docstring) and call the diff out in the PR — sharded "
        "arenas serialized under the old layout will NOT survive it.")


def test_shard_layout_embeds_per_shard_arena_layout():
    """A shard's layout IS the single-arena layout of the per-shard
    config — the property that lets arena.split/join and both kernel
    lowerings run per shard unchanged."""
    for kind in arena.KINDS:
        for family in arena.QUEUE_FAMILIES:
            slay = shards.layout(CFG, SHARDS, kind, family)
            scfg = shards.shard_config(CFG, SHARDS)
            assert slay.shard is arena.layout(scfg, kind, family)
            assert slay.mem_words == slay.shard.mem_words
            assert slay.shard_words * SHARDS == CFG.total_words


def test_shard_split_join_roundtrip():
    """shards.split_regions/join_regions (the sharded blocked
    wrapper's mem plumbing) is lossless over the stacked image."""
    import jax.numpy as jnp
    slay = shards.layout(CFG, SHARDS, "chunk", "vl")
    mem = jnp.arange(SHARDS * slay.mem_words,
                     dtype=jnp.int32).reshape(SHARDS, slay.mem_words)
    parts = shards.split_regions(slay, mem)
    assert set(parts) == {r.name for r in slay.shard.regions}
    assert (shards.join_regions(slay, parts) == mem).all()
