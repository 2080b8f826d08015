"""Live defragmentation: on-device page migration with a plan/execute split.

`transactions.compact` (DESIGN.md §5b) releases sticky chunk→class
bindings but never moves a live word, so a long-running heap slowly
strands physical pages: chunks stay bound while only a few of their
pages are live, the pool drains, and eventually a request fails even
though most of the heap is free.  This module is the true defrag pass —
the first subsystem where the allocator rewrites its own heap:

``plan``     a pure-jnp **relocation plan** from arena state: for each
             size class, rank the bound chunks densest-first, keep the
             minimal prefix that can hold every live page (the
             *receivers*), and move every live page of the remaining
             *donor* chunks into the receivers' free slots — sources
             ordered (chunk-rank, page) ascending, destinations
             likewise, k-th source paired with k-th destination.  The
             plan is a fixed-width **forwarding table**
             ``(src, dst, sizes)`` of old→new word offsets (−1 padded),
             shared verbatim by every backend (like
             ``shards.home_shards``) so execution can never diverge.

``migrate``  the **execute** step ``(mem, ctl, plan) → (mem', ctl')``:
             copy each extent's heap words, flip its bitmap bits, move
             the free counts, then run the class-major rebuild — unbind
             fully-free chunks, re-prime the pool with them, and
             rebuild each class queue (ring row / directory / vl chain)
             from the surviving live chunks.  An empty plan degenerates
             to exactly a ``compact``-style rebuild.  This math is the
             jnp oracle AND the body of the whole-lowering kernel
             (kernels/defrag_txn.py); the blocked lowering
             re-expresses it as §8 scalar-core code, and a
             wave is ONE ``pallas_call`` under both (DESIGN.md §10,
             tests/test_defrag.py).

The sharded execute (``sharded_migrate_math``) runs the same moves as a
two-phase (phase, shard) schedule — extract every source shard's pages
into a carry buffer, then insert + rebuild every shard — so ONE wave
also covers **cross-shard rebalancing**: ``shards.rebalance_plan_math``
emits moves from the most- to the least-loaded shard and the very same
kernel executes them.

Defragmentation applies to chunk kinds only (page kinds carve their
inventory at init and never bind chunks); page-kind plans are empty and
their waves are no-ops.

Plans are inspectable without running anything:

>>> import jax.numpy as jnp
>>> from repro.core import HeapConfig, defrag, transactions
>>> cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
...                  min_page_bytes=16)
>>> st = transactions.init(cfg, "chunk", "ring")
>>> ones = jnp.ones(8, bool)
>>> sizes = jnp.full(8, 16, jnp.int32)
>>> st, offs = transactions.alloc(cfg, "chunk", "ring", st, sizes, ones)
>>> src, dst, sz = defrag.plan_math(cfg, "chunk", "ring", st.mem,
...                                 st.ctl, max_moves=16)
>>> int((src >= 0).sum())          # dense heap: nothing to migrate
0
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import arena, chunk_alloc, groups, queues
from repro.core.heap import HeapConfig, size_to_class_device

# Default forwarding-table width: enough for every realistic wave on
# the serving heap; callers needing a bigger single wave pass an
# explicit max_moves (the bound is static — it shapes the kernel).
DEFAULT_MAX_MOVES = 128


class Forwarding(NamedTuple):
    """One wave's old→new relocation table (−1-padded lanes are no-ops).

    ``src``/``dst`` are heap word offsets (GLOBAL offsets for sharded
    arenas), ``sizes`` the extent sizes in bytes (the page size of the
    extent's class) — exactly the ``(offsets, sizes)`` vocabulary of
    ``alloc``/``free``, so callers remap their references with
    :func:`forward_offsets` / ``kv_cache.apply_forwarding``.
    """
    src: Any    # (M,) int32
    dst: Any    # (M,) int32
    sizes: Any  # (M,) int32


def empty_forwarding(max_moves: int = 0) -> Forwarding:
    return Forwarding(src=jnp.full(max_moves, -1, jnp.int32),
                      dst=jnp.full(max_moves, -1, jnp.int32),
                      sizes=jnp.zeros(max_moves, jnp.int32))


def forward_offsets(fwd: Forwarding, offsets_words):
    """Remap word offsets through the forwarding table (offsets not in
    the table pass through unchanged, including −1 lanes)."""
    src = jnp.where(fwd.src >= 0, fwd.src, jnp.int32(-2))
    hit = offsets_words[:, None] == src[None, :]
    new = jnp.sum(jnp.where(hit, fwd.dst[None, :], 0), axis=1)
    return jnp.where(hit.any(axis=1), new, offsets_words)


# --------------------------------------------------------------------------
# plan: pick live extents in the sparsest chunks, assign dense targets
# --------------------------------------------------------------------------

def _occupancy_bits(bitmap):
    """(nc, bw) uint32 occupancy → (nc, bw·32) bool, bit order LSB-first
    (the layout ``chunk_alloc._expand_bitmap`` reads)."""
    nc, bw = bitmap.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (bitmap[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(nc, bw * 32).astype(bool)


def _take_bits(bits, order, limit, off_of_bit, max_moves: int):
    """The first ``limit`` set bits of ``bits``, visiting chunks in
    ``order`` and pages ascending within each chunk; returns their word
    offsets scattered to positions [0, count) of a (max_moves,) array
    (−1 padded) plus the count."""
    b = bits[order].reshape(-1)
    o = off_of_bit[order].reshape(-1)
    bi = b.astype(jnp.int32)
    ordinal = jnp.cumsum(bi) - bi
    take = b & (ordinal < limit)
    out = jnp.full(max_moves, -1, jnp.int32).at[
        jnp.where(take, ordinal, max_moves)].set(o, mode="drop")
    return out, jnp.minimum(jnp.sum(bi), limit)


def plan_math(cfg: HeapConfig, kind: str, family: str, mem, ctl, *,
              max_moves: int = DEFAULT_MAX_MOVES):
    """Relocation plan for one arena (the jnp oracle — every backend
    executes this exact table).  Returns ``(src, dst, sizes)`` local
    word offsets, −1 padded to ``max_moves``.

    Per class: chunks ranked densest-first (live pages descending, id
    ascending); the minimal receiver prefix that can hold all live
    pages keeps them, every other bound chunk donates.  Any prefix of
    the table is a valid (smaller) wave — destinations are slots that
    were free *before* the wave and never slots another move vacates —
    so ``max_moves`` truncation is safe and later waves converge."""
    if kind != "chunk":
        f = empty_forwarding(max_moves)
        return f.src, f.dst, f.sizes
    lay = arena.layout(cfg, kind, family)
    _, _, meta = arena.unpack(lay, arena.Arena(mem, ctl))
    nc = cfg.num_chunks
    wpc = cfg.words_per_chunk
    maxbits = cfg.bitmap_words_per_chunk * 32
    C = cfg.num_classes
    ids = jnp.arange(nc, dtype=jnp.int32)
    bitpos = jnp.arange(maxbits, dtype=jnp.int32)
    occ = _occupancy_bits(meta.bitmap)

    src = jnp.full(max_moves, -1, jnp.int32)
    dst = jnp.full(max_moves, -1, jnp.int32)
    sz = jnp.zeros(max_moves, jnp.int32)
    base = jnp.int32(0)
    for c in range(C):
        ppc = cfg.pages_per_chunk(c)
        pw = cfg.page_words(c)
        bound = meta.chunk_class == c
        in_range = bitpos[None, :] < ppc
        live = jnp.where(bound, ppc - meta.free_count, 0)
        need = (jnp.sum(live) + ppc - 1) // ppc
        # densest bound chunks first, unbound chunks last (unique keys)
        key = jnp.where(bound, (ppc - live) * nc + ids,
                        (ppc + 1) * nc + ids)
        order = jnp.argsort(key)
        rank = jnp.zeros(nc, jnp.int32).at[order].set(ids)
        is_recv = bound & (rank < need)
        is_donor = bound & (rank >= need)
        src_bits = occ & is_donor[:, None] & in_range
        dst_bits = (~occ) & is_recv[:, None] & in_range
        budget = jnp.clip(max_moves - base, 0,
                          jnp.sum(src_bits.astype(jnp.int32)))
        off_of = ids[:, None] * wpc + bitpos[None, :] * pw
        s_off, cnt = _take_bits(src_bits, order, budget, off_of,
                                max_moves)
        d_off, _ = _take_bits(dst_bits, order, budget, off_of,
                              max_moves)
        k = jnp.arange(max_moves, dtype=jnp.int32)
        pos = jnp.where(k < cnt, base + k, max_moves)
        src = src.at[pos].set(s_off, mode="drop")
        dst = dst.at[pos].set(d_off, mode="drop")
        sz = sz.at[pos].set(cfg.page_bytes(c), mode="drop")
        base = base + cnt
    return src, dst, sz


def sharded_plan_math(cfg: HeapConfig, num_shards: int, kind: str,
                      family: str, mem, ctl, *,
                      max_moves: int = DEFAULT_MAX_MOVES):
    """Per-shard compaction plans merged into one GLOBAL-offset table
    (shards are independent heaps, so in-shard plans compose by
    concatenation; cross-shard moves are ``shards.rebalance_plan_math``'s
    job)."""
    from repro.core import shards  # lazy: defrag <-> shards
    if kind != "chunk":
        f = empty_forwarding(max_moves)
        return f.src, f.dst, f.sizes
    scfg = shards.shard_config(cfg, num_shards)
    Ws = scfg.total_words
    src = jnp.full(max_moves, -1, jnp.int32)
    dst = jnp.full(max_moves, -1, jnp.int32)
    sz = jnp.zeros(max_moves, jnp.int32)
    base = jnp.int32(0)
    k = jnp.arange(max_moves, dtype=jnp.int32)
    for s in range(num_shards):
        s_src, s_dst, s_sz = plan_math(scfg, kind, family, mem[s],
                                       ctl[s], max_moves=max_moves)
        cnt = jnp.sum((s_src >= 0).astype(jnp.int32))
        cnt = jnp.minimum(cnt, max_moves - base)
        pos = jnp.where(k < cnt, base + k, max_moves)
        src = src.at[pos].set(s_src + s * Ws, mode="drop")
        dst = dst.at[pos].set(s_dst + s * Ws, mode="drop")
        sz = sz.at[pos].set(s_sz, mode="drop")
        base = base + cnt
    return src, dst, sz


# --------------------------------------------------------------------------
# execute: extract / insert+rebuild (the migration oracle)
# --------------------------------------------------------------------------
#
# The execute math is split so the sharded schedule can reuse it: a
# wave is extract (gather each source extent's words into its carry-
# buffer row, clear its bits, return its pages to the free counts)
# followed by insert+rebuild (write the buffered words at the
# destinations, set bits, then the class-major rebuild).  The
# single-arena migrate is the composition on one arena; the sharded
# migrate runs extract over every shard, then insert+rebuild over
# every shard (phase-major, shard-minor — the schedule both Pallas
# lowerings grid into ONE pallas_call).

def _move_lanes(cfg: HeapConfig, offsets, sizes, sel):
    C = cfg.num_classes
    cls = size_to_class_device(cfg, sizes)
    valid = sel & (offsets >= 0) & (cls < C)
    pw = jnp.left_shift(cfg.page_words(0), cls % C).astype(jnp.int32)
    return valid, pw


def extract_math(cfg: HeapConfig, kind: str, family: str, mem, ctl,
                 src, sizes, sel, buf):
    """Phase-0 of a wave on one arena: buffer the selected extents'
    heap words, clear their bitmap bits, bump their chunks' free
    counts.  Queues/ctl are untouched (the rebuild happens at insert).
    Returns ``(mem', buf')``."""
    lay = arena.layout(cfg, kind, family)
    q, ctx, meta = arena.unpack(lay, arena.Arena(mem, ctl))
    W = cfg.total_words
    wpc = cfg.words_per_chunk
    maxw = wpc
    valid, pw = _move_lanes(cfg, src, sizes, sel)
    j = jnp.arange(maxw, dtype=jnp.int32)[None, :]
    ok = valid[:, None] & (j < pw[:, None])
    words = jnp.where(ok, src[:, None] + j, W)
    vals = ctx.heap.at[words].get(mode="fill", fill_value=0)
    buf = jnp.where(ok, vals, buf)
    chunk = jnp.where(valid, src // wpc, cfg.num_chunks)
    page = jnp.where(valid, (src % wpc) // pw, 0)
    meta = chunk_alloc._set_bits(meta, chunk, page, valid, -1)
    return arena.pack(lay, q, ctx, meta).mem, buf


def insert_rebuild_math(cfg: HeapConfig, kind: str, family: str, mem,
                        ctl, dst, sizes, sel, buf):
    """Phase-1 of a wave on one arena: write the buffered extents at
    their destinations, set their bits, then the class-major rebuild
    (unbind fully-free chunks → fresh pool → per-class queue rebuild).
    Returns ``(mem', ctl')`` — runs even for an empty selection, where
    it degenerates to the compact-style rebuild."""
    lay = arena.layout(cfg, kind, family)
    q, ctx, meta = arena.unpack(lay, arena.Arena(mem, ctl))
    C = cfg.num_classes
    nc = cfg.num_chunks
    W = cfg.total_words
    wpc = cfg.words_per_chunk
    maxw = wpc
    valid, pw = _move_lanes(cfg, dst, sizes, sel)

    # insert the buffered words
    j = jnp.arange(maxw, dtype=jnp.int32)[None, :]
    ok = valid[:, None] & (j < pw[:, None])
    words = jnp.where(ok, dst[:, None] + j, W)
    heap = ctx.heap.at[words].set(buf, mode="drop")
    ctx = ctx._replace(heap=heap)
    chunk = jnp.where(valid, dst // wpc, nc)
    page = jnp.where(valid, (dst % wpc) // pw, 0)

    # destination chunks still unbound (cross-shard rebalance targets
    # the receiver's pool chunks) are claimed first — bitmap reset,
    # full free count, bound to the move's class — exactly alloc's
    # from-pool path; the rebuild below then keeps them out of the
    # fresh pool because they are bound now.
    cls = size_to_class_device(cfg, sizes)
    claimed = jnp.zeros(nc, bool).at[chunk].set(
        True, mode="drop") & (meta.chunk_class < 0)
    ppc_move = jnp.right_shift(cfg.max_pages_per_chunk,
                               jnp.clip(cls, 0, C - 1))
    bitmap = jnp.where(claimed[:, None], jnp.uint32(0), meta.bitmap)
    fc = meta.free_count.at[jnp.where(valid & claimed[chunk % nc],
                                      chunk, nc)].set(
        ppc_move, mode="drop")
    cc0 = meta.chunk_class.at[jnp.where(valid & claimed[chunk % nc],
                                        chunk, nc)].set(
        cls, mode="drop")
    meta = meta._replace(bitmap=bitmap, free_count=fc, chunk_class=cc0)
    meta = chunk_alloc._set_bits(meta, chunk, page, valid, +1)

    # unbind fully-free chunks, re-prime the pool with every unbound id
    maxppc = cfg.max_pages_per_chunk
    cc = meta.chunk_class
    full_count = jnp.right_shift(maxppc, jnp.clip(cc, 0, C - 1))
    fully_free = (cc >= 0) & (meta.free_count == full_count)
    cc = jnp.where(fully_free, -1, cc)
    meta = meta._replace(chunk_class=cc)
    ids = jnp.arange(nc, dtype=jnp.int32)
    unbound = cc < 0
    rank = groups.masked_prefix_sum(jnp.ones(nc, jnp.int32), unbound)
    pool, _ = queues.ring_bulk_enqueue(
        cfg, queues.ring_init(1, nc), None, jnp.zeros(nc, jnp.int32),
        rank, ids, unbound)
    ctx = queues.AllocCtx(heap=ctx.heap, pool=pool)

    # class-major queue rebuild (matches the blocked lowering's class
    # loop step for step — every pool pop happens in class order)
    fam = queues.FAMILIES[family]
    if family == "ring":
        q = queues.ring_init(C, lay.queue_capacity)
    else:
        q = queues.VirtState(
            directory=jnp.full((C, lay.max_segs), queues.NULL, jnp.int32),
            head=jnp.full(C, queues.NULL, jnp.int32),
            tail=jnp.full(C, queues.NULL, jnp.int32),
            front=jnp.zeros(C, jnp.int32), back=jnp.zeros(C, jnp.int32))
    for c in range(C):
        live_c = (cc == c) & (meta.free_count > 0)
        if family != "ring":
            # one fresh segment per class, popped in class order
            pool2, seg0 = queues.pool_dequeue(cfg, ctx.pool,
                                              jnp.ones(1, bool))
            ctx = ctx._replace(pool=pool2)
            s0 = seg0[0]
            if family == "vl":
                w0 = s0 * wpc
                heap = ctx.heap.at[jnp.where((w0 >= 0) & (w0 < W),
                                             w0, W)].set(
                    queues.NULL, mode="drop")
                ctx = ctx._replace(heap=heap)
            else:
                q = q._replace(directory=q.directory.at[c, 0].set(s0))
            q = q._replace(head=q.head.at[c].set(s0),
                           tail=q.tail.at[c].set(s0))
        rk = groups.masked_prefix_sum(jnp.ones(nc, jnp.int32), live_c)
        q, ctx = fam.bulk_enqueue(cfg, q, ctx, jnp.full(nc, c, jnp.int32),
                                  rk, ids, live_c)
    # a defrag wave is not allocator traffic: the ctl telemetry region
    # (DESIGN.md §14) carries through unchanged — matching the blocked
    # kernels, which stage the full ctl block and rewrite core words only
    new = arena.pack(lay, q, ctx, meta, tele=arena.tele_of(lay, ctl))
    return new.mem, new.ctl


def migrate_math(cfg: HeapConfig, kind: str, family: str, mem, ctl,
                 src, dst, sizes):
    """One whole migration wave on one arena (extract → insert →
    class-major rebuild): the jnp oracle AND the whole-lowering kernel
    body.  Returns ``(mem', ctl')``."""
    if kind != "chunk":
        return mem, ctl
    M = src.shape[0]
    buf = jnp.zeros((M, cfg.words_per_chunk), jnp.int32)
    valid = (src >= 0) & (dst >= 0)
    mem, buf = extract_math(cfg, kind, family, mem, ctl, src, sizes,
                            valid, buf)
    return insert_rebuild_math(cfg, kind, family, mem, ctl, dst, sizes,
                               valid, buf)


def sharded_migrate_math(cfg: HeapConfig, num_shards: int, kind: str,
                         family: str, mem, ctl, src, dst, sizes):
    """Sharded wave: extract over every shard, then insert+rebuild over
    every shard (phase-major, shard-minor — the serial replay both
    Pallas lowerings grid).  Cross-shard moves ride the carry buffer
    between the phases; every shard is rebuilt, so donors retire their
    emptied chunks in the same wave.  Returns ``(mem', ctl')``."""
    from repro.core import shards  # lazy: defrag <-> shards
    if kind != "chunk":
        return mem, ctl
    scfg = shards.shard_config(cfg, num_shards)
    Ws = scfg.total_words
    M = src.shape[0]
    buf = jnp.zeros((M, scfg.words_per_chunk), jnp.int32)
    src_sh = jnp.where(src >= 0, src // Ws, -1)
    dst_sh = jnp.where(dst >= 0, dst // Ws, -1)
    valid = (src >= 0) & (dst >= 0)

    def ext_step(carry, s):
        mem, buf = carry
        sel = valid & (src_sh == s)
        local = jnp.where(sel, src - s * Ws, -1)
        m2, buf = extract_math(
            scfg, kind, family,
            jax.lax.dynamic_index_in_dim(mem, s, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(ctl, s, 0, keepdims=False),
            local, sizes, sel, buf)
        return (jax.lax.dynamic_update_index_in_dim(mem, m2, s, 0),
                buf), None

    def ins_step(carry, s):
        mem, ctl, buf = carry
        sel = valid & (dst_sh == s)
        local = jnp.where(sel, dst - s * Ws, -1)
        m2, c2 = insert_rebuild_math(
            scfg, kind, family,
            jax.lax.dynamic_index_in_dim(mem, s, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(ctl, s, 0, keepdims=False),
            local, sizes, sel, buf)
        mem = jax.lax.dynamic_update_index_in_dim(mem, m2, s, 0)
        ctl = jax.lax.dynamic_update_index_in_dim(ctl, c2, s, 0)
        return (mem, ctl, buf), None

    srange = jnp.arange(num_shards, dtype=jnp.int32)
    (mem, buf), _ = jax.lax.scan(ext_step, (mem, buf), srange)
    (mem, ctl, _), _ = jax.lax.scan(ins_step, (mem, ctl, buf), srange)
    return mem, ctl


# --------------------------------------------------------------------------
# fragmentation observability
# --------------------------------------------------------------------------

def _pool_members(cfg: HeapConfig, pool):
    """Bool mask over chunk ids: currently queued in the free pool."""
    nc = cfg.num_chunks
    cnt = (pool.back - pool.front)[0]
    k = jnp.arange(nc, dtype=jnp.int32)
    slots = (pool.front[0] + k) % nc
    ids = pool.store[0, slots]
    live = k < cnt
    return jnp.zeros(nc, bool).at[
        jnp.where(live & (ids >= 0) & (ids < nc), ids, nc)].set(
        True, mode="drop")


def frag_stats_math(cfg: HeapConfig, kind: str, family: str, mem, ctl):
    """``(free_words, largest_free_extent)`` of one arena.

    Chunk kinds: word-exact — a word is free iff its chunk sits in the
    pool (fully reusable) or it belongs to a free page of a bound
    chunk; the largest extent is the longest contiguous free run.
    Page kinds carve inventory at init, so free words are the queued
    per-class inventories and the largest extent is the largest page
    still grantable (the allocator can never grant more contiguously).
    """
    lay = arena.layout(cfg, kind, family)
    C = cfg.num_classes
    if kind != "chunk":
        front = ctl[lay.off_front:lay.off_front + C]
        back = ctl[lay.off_back:lay.off_back + C]
        counts = back - front
        pws = jnp.array([cfg.page_words(c) for c in range(C)], jnp.int32)
        free_words = jnp.sum(counts * pws)
        largest = jnp.max(jnp.where(counts > 0, pws, 0))
        return free_words, largest
    _, ctx, meta = arena.unpack(lay, arena.Arena(mem, ctl))
    nc = cfg.num_chunks
    wpc = cfg.words_per_chunk
    maxbits = cfg.bitmap_words_per_chunk * 32
    occ = _occupancy_bits(meta.bitmap)
    bound = meta.chunk_class >= 0
    cc = jnp.clip(meta.chunk_class, 0, C - 1)
    pw = jnp.left_shift(cfg.page_words(0), cc).astype(jnp.int32)
    ppc = jnp.right_shift(cfg.max_pages_per_chunk, cc)
    free_page = (~occ) & bound[:, None] \
        & (jnp.arange(maxbits, dtype=jnp.int32)[None, :] < ppc[:, None])
    word_page = jnp.minimum(
        jnp.arange(wpc, dtype=jnp.int32)[None, :] // pw[:, None],
        maxbits - 1)
    in_pool = _pool_members(cfg, ctx.pool)
    free_mask = (in_pool[:, None]
                 | (bound[:, None] & jnp.take_along_axis(
                     free_page, word_page, axis=1))).reshape(-1)
    idx = jnp.arange(free_mask.shape[0], dtype=jnp.int32)
    last_blocked = jax.lax.cummax(jnp.where(~free_mask, idx, -1))
    run = jnp.where(free_mask, idx - last_blocked, 0)
    return jnp.sum(free_mask.astype(jnp.int32)), jnp.max(run)


def frag_ratio(free_words, largest_free_extent):
    """``1 − largest_free/total_free`` ∈ [0, 1): 0 = one solid free
    block, → 1 = free space shattered into small extents."""
    total = jnp.maximum(free_words, 1)
    r = 1.0 - largest_free_extent.astype(jnp.float32) / total
    return jnp.where(free_words > 0, r, 0.0)
