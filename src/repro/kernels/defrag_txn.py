"""Pallas kernels: one fused migration wave per ``pallas_call``.

The defragmentation execute step (core/defrag.py, DESIGN.md §10) —
copy each planned extent's heap words, flip its occupancy bits, move
the free counts, retire emptied chunks to the pool, and rebuild the
class queues — runs as ONE ``pallas_call`` per wave under both kernel
lowerings, exactly like the alloc/free transactions:

``arena_defrag_txn`` (whole lowering)
    the kernel body IS ``defrag.migrate_math`` over full ``mem``/``ctl``
    refs (parity with the jnp oracle is structural, as in
    kernels/alloc_txn.arena_*_txn); ``mem``/``ctl`` are input/output-
    aliased so the wave rewrites the arena in place.

``arena_defrag_txn_blocked`` (blocked lowering, the one the chip runs)
    the wave as scalar-core code over the region windows of
    kernels/alloc_txn_blocked.py: every region is an aliased HBM slab
    reached through a 1024-word SMEM window, the control block lives in
    SMEM.  The kernel extracts every source extent into an HBM carry
    buffer, inserts at the destinations (word copies, bit flips), then
    unbinds emptied chunks, re-primes the pool and rebuilds each class
    queue in the oracle's class-major order.

``sharded_arena_defrag_txn`` / ``sharded_arena_defrag_txn_blocked``
    the (phase, shard) schedule of ``defrag.sharded_migrate_math`` —
    phase 0 extracts every source shard's extents into the carry
    buffer, phase 1 inserts and rebuilds every shard — so a single
    wave covers in-shard compaction AND cross-shard rebalance moves.
    The whole lowering grids (2, S) over shard slabs; the blocked
    lowering loops over the shards, addressing shard ``s`` at its
    slab base in every region.

The plan (``src``/``dst``/``sizes`` forwarding table) is computed once
in pure jnp and shared by every backend; the execute contract assumes
what the planners guarantee — source extents and destination slots are
disjoint — so extract-then-insert equals a simultaneous move.
tests/test_defrag.py holds every implementation word-identical to the
oracle and asserts the one-kernel property on the jaxpr.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import arena
from repro.core.heap import size_to_class_device
from repro.kernels.alloc_txn_blocked import (BLOCK, NULL, _bind, _copy,
                                             _fill, _flip_page, _i32, _ld,
                                             _loop, _pool_pop1, _slab, _st,
                                             _st_if, _unslab, _va_grow,
                                             _View, _vl_grow, launch,
                                             segs_words)


# --------------------------------------------------------------------------
# whole lowering: the kernel body is the oracle itself
# --------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("cfg", "kind", "family", "interpret"))
def arena_defrag_txn(cfg, kind, family, mem, ctl, src, dst, sizes, *,
                     interpret: bool = False):
    """One whole migration wave as ONE pallas_call (whole lowering).
    Returns ``(new_mem, new_ctl)`` — bit-identical to
    ``defrag.migrate_math``, which is also the kernel body."""
    from repro.core import defrag  # lazy: kernels <-> core

    def kernel(mem_ref, ctl_ref, src_ref, dst_ref, sizes_ref,
               omem_ref, octl_ref):
        nm, nc2 = defrag.migrate_math(
            cfg, kind, family, mem_ref[...], ctl_ref[...], src_ref[...],
            dst_ref[...], sizes_ref[...])
        omem_ref[...] = nm
        octl_ref[...] = nc2

    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(mem.shape, jnp.int32),
                   jax.ShapeDtypeStruct(ctl.shape, jnp.int32)],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(mem, ctl, src.astype(jnp.int32), dst.astype(jnp.int32),
      sizes.astype(jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("cfg", "num_shards", "kind", "family",
                                    "interpret"))
def sharded_arena_defrag_txn(cfg, num_shards, kind, family, mem, ctl,
                             src, dst, sizes, *,
                             interpret: bool = False):
    """Sharded wave: ONE pallas_call gridding the (phase, shard)
    schedule — phase 0 extracts each source shard into the carry
    buffer, phase 1 inserts + rebuilds each shard.  Bit-identical to
    ``defrag.sharded_migrate_math`` (the kernel body reuses its
    extract/insert math per shard row)."""
    from repro.core import defrag, shards  # lazy: kernels <-> core

    S = num_shards
    scfg = shards.shard_config(cfg, S)
    Ws = scfg.total_words
    Mw, Cw = mem.shape[1], ctl.shape[1]
    M = src.shape[0]
    maxw = scfg.words_per_chunk

    def kernel(mem_ref, ctl_ref, src_ref, dst_ref, sizes_ref,
               omem_ref, octl_ref, buf_ref):
        p = pl.program_id(0)
        s = pl.program_id(1)

        @pl.when((p == 0) & (s == 0))
        def _first():
            buf_ref[...] = jnp.zeros((M, maxw), jnp.int32)

        @pl.when(p == 0)
        def _stage():  # first visit of shard s: boundary state in
            omem_ref[...] = mem_ref[...]
            octl_ref[...] = ctl_ref[...]

        srcv = src_ref[...]
        dstv = dst_ref[...]
        sizv = sizes_ref[...]
        valid = (srcv >= 0) & (dstv >= 0)

        @pl.when(p == 0)
        def _extract():
            sel = valid & (srcv // Ws == s)
            local = jnp.where(sel, srcv - s * Ws, -1)
            nm, nbuf = defrag.extract_math(
                scfg, kind, family, omem_ref[0, :], octl_ref[0, :],
                local, sizv, sel, buf_ref[...])
            omem_ref[0, :] = nm
            buf_ref[...] = nbuf

        @pl.when(p == 1)
        def _insert():
            sel = valid & (dstv // Ws == s)
            local = jnp.where(sel, dstv - s * Ws, -1)
            nm, nc2 = defrag.insert_rebuild_math(
                scfg, kind, family, omem_ref[0, :], octl_ref[0, :],
                local, sizv, sel, buf_ref[...])
            omem_ref[0, :] = nm
            octl_ref[0, :] = nc2

    lane = pl.BlockSpec((M,), lambda p, s: (0,))
    outs = pl.pallas_call(
        kernel,
        grid=(2, S),
        in_specs=[pl.BlockSpec((1, Mw), lambda p, s: (s, 0)),
                  pl.BlockSpec((1, Cw), lambda p, s: (s, 0)),
                  lane, lane, lane],
        out_specs=[pl.BlockSpec((1, Mw), lambda p, s: (s, 0)),
                   pl.BlockSpec((1, Cw), lambda p, s: (s, 0)),
                   pl.BlockSpec((M, maxw), lambda p, s: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((S, Mw), jnp.int32),
                   jax.ShapeDtypeStruct((S, Cw), jnp.int32),
                   jax.ShapeDtypeStruct((M, maxw), jnp.int32)],
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(mem, ctl, src.astype(jnp.int32), dst.astype(jnp.int32),
      sizes.astype(jnp.int32))
    return outs[0], outs[1]


# --------------------------------------------------------------------------
# blocked lowering: the wave as scalar-core code over region windows
# --------------------------------------------------------------------------
#
# Every region is both read and written, so every region is an aliased
# HBM slab reached through its window (alloc_txn_blocked.launch); the
# carry buffer is one more HBM output.  The kernel runs the extract
# phase over every shard, then per shard the insert phase, the unbind
# and pool re-prime, and the class-major queue rebuild.

def _moves(cfg, ins, sharded, s, phase):
    """Move ``i`` of this (phase, shard) visit: (selected, shard-local
    offset, page words, class).  A move is selected when both ends are
    planned, its class is valid and (sharded) its end for this phase
    lives on shard ``s``."""
    C = cfg.num_classes
    Ws = cfg.total_words
    src, dst, cls = ins[1], ins[2], ins[3]

    def mv(i):
        end = (src if phase == "extract" else dst)[i]
        ok = (src[i] >= 0) & (dst[i] >= 0) & (cls[i] < C)
        if sharded:
            ok &= end // Ws == s
        local = end - s * Ws if sharded else end
        pw = jnp.left_shift(_i32(cfg.page_words(0)), cls[i] % C)
        return ok, jnp.where(ok, local, 0), pw, cls[i]

    return mv


def _extract_moves(cfg, E, carry, mv, M):
    """Per move: copy its page words into its carry row, clear its
    bitmap bit, give the page back to its chunk's free count —
    ``defrag.extract_math`` one word at a time."""
    maxw = cfg.words_per_chunk
    heap = E["heap"]

    def move(i, _):
        g, s, pw, _c = mv(i)

        @pl.when(g)
        def _():
            def cp(k, _):
                _st(carry, i * maxw + k, _ld(heap, s + k))
                return 0

            _loop(0, pw, cp)
            _flip_page(cfg, E, s, pw, -1)
        return 0

    _loop(0, M, move)


def _insert_moves(cfg, E, carry, mv, M):
    """Per move: place its carry row at the destination, claim the
    destination chunk if it is still unbound (bitmap reset, full count,
    bind — alloc's from-pool path, which cross-shard rebalance moves
    rely on), set the bit, take the page from the free count —
    ``defrag.insert_rebuild_math``'s insert half."""
    wpc = cfg.words_per_chunk
    maxw = wpc
    heap = E["heap"]

    def move(i, _):
        g, d, pw, cls = mv(i)

        @pl.when(g)
        def _():
            def cp(k, _):
                _st(heap, d + k, _ld(carry, i * maxw + k))
                return 0

            _loop(0, pw, cp)
            ch = d // wpc

            @pl.when(_ld(E["chunk_class"], ch) < 0)
            def _claim():
                _bind(cfg, E, ch, cls)

            _flip_page(cfg, E, d, pw, 1)
        return 0

    _loop(0, M, move)


def _unbind_and_pool(cfg, lay, E, octl):
    """Unbind fully-free chunks and re-prime the pool ring with every
    unbound id (ascending), NULL behind them."""
    C = cfg.num_classes
    nc = cfg.num_chunks
    cc, fc, pool = E["chunk_class"], E["free_count"], E["pool_store"]

    def scan(k, n_un):
        ccv = _ld(cc, k)
        full = jnp.right_shift(_i32(cfg.max_pages_per_chunk),
                               jnp.clip(ccv, 0, C - 1))
        cc2 = jnp.where((ccv >= 0) & (_ld(fc, k) == full), -1, ccv)
        _st(cc, k, cc2)
        _st_if(pool, n_un, k, cc2 < 0)
        return n_un + (cc2 < 0).astype(jnp.int32)

    k = _loop(0, nc, scan)
    _fill(pool, k, nc - k, NULL)
    _st(octl, lay.off_pool_front, 0)
    _st(octl, lay.off_pool_back, k)


def _rebuild_class(cfg, lay, family, c, E, octl, segs):
    """Rebuild class ``c``'s queue from the surviving live chunks —
    the class step of the oracle's class-major rebuild (fresh
    counters, one fresh segment for virtualized families, then the
    ascending-id enqueue of every bound chunk with free pages)."""
    nc = cfg.num_chunks
    wpc = cfg.words_per_chunk
    W = cfg.total_words
    spc = cfg.slots_per_segment(family)
    max_segs = lay.max_segs
    cc, fc = E["chunk_class"], E["free_count"]
    pool = E["pool_store"]
    heap = E.get("heap")

    def live(k):
        return (_ld(cc, k) == c) & (_ld(fc, k) > 0)

    def for_live(fn):
        def step(k, r):
            lv = live(k)

            @pl.when(lv)
            def _():
                fn(k, r)

            return r + lv.astype(jnp.int32)

        return _loop(0, nc, step)

    cnt = _loop(0, nc, lambda k, a: a + live(k).astype(jnp.int32))
    _st(octl, lay.off_front + c, 0)
    _st(octl, lay.off_back + c, 0)

    if family == "ring":
        q = E["queue_store"]
        cap = lay.queue_capacity
        _fill(q, c * cap, cap, NULL)
        _st(octl, lay.off_head + c, 0)
        _st(octl, lay.off_tail + c, 0)
        for_live(lambda k, r: _st(q, c * cap + r % cap, k))
        _st(octl, lay.off_back + c, cnt)
        return

    # virtualized families: one fresh segment, popped in class order
    dirv = E["directory"]
    row = c * max_segs
    _fill(dirv, row, max_segs, NULL)
    s0 = _pool_pop1(octl, pool, lay, True)
    if family == "va":
        _st(dirv, row, s0)
    else:  # vl: terminate the fresh head segment
        _st_if(heap, s0 * wpc, NULL, (s0 * wpc >= 0) & (s0 * wpc < W))
    _st(octl, lay.off_head + c, s0)
    _st(octl, lay.off_tail + c, s0)

    if family == "va":
        _va_grow(octl, pool, dirv, lay, c, spc, _i32(0), cnt)

        def put(k, r):
            seg = _ld(dirv, row + (r // spc) % max_segs)
            word = seg * wpc + r % spc
            _st_if(heap, word, k, (word >= 0) & (word < W))
    else:  # vl
        new_tail = _vl_grow(octl, pool, heap, segs, lay, spc, s0, _i32(0),
                            cnt)

        def put(k, r):
            word = segs[r // spc] * wpc + 1 + r % spc
            _st_if(heap, word, k, (word >= 0) & (word < W))

    for_live(put)
    if family == "vl":
        _st(octl, lay.off_tail + c, new_tail)
    _st(octl, lay.off_back + c, cnt)


def _wave(cfg, S, sharded, kind, family, parts, ctl, src, dst, sizes,
          interpret):
    """One migration wave over ``S`` arena slabs (``parts``: ``{region:
    (S, words)}``, ``ctl``: ``(S, ctl_words)``) as ONE pallas_call."""
    assert kind == "chunk", "defrag waves exist for chunk kinds only"
    lay = arena.layout(cfg, kind, family)
    C = cfg.num_classes
    Cw = lay.ctl_words
    M = src.shape[0]
    names = [r.name for r in lay.regions]
    hbm, stride = {}, {}
    for nm in names:
        hbm[nm], stride[nm] = _slab(parts[nm])
    carry_words = -(-M * cfg.words_per_chunk // BLOCK) * BLOCK
    smem_in = [ctl.reshape(-1).astype(jnp.int32), src.astype(jnp.int32),
               dst.astype(jnp.int32),
               size_to_class_device(cfg, sizes.astype(jnp.int32))]

    def body(ins, wins, outs, scr):
        octl_ref = outs[0]
        segs = scr[0]
        carry = _View(win=wins["carry"])
        _copy(ins[0], octl_ref, S * Cw)

        def views(s):
            return ({nm: _View(base=s * stride[nm], win=wins[nm])
                     for nm in names}, _View(octl_ref, base=s * Cw))

        def extract(s, _):
            E, _octl = views(s)
            _extract_moves(cfg, E, carry,
                           _moves(cfg, ins, sharded, s, "extract"), M)
            return 0

        def insert(s, _):
            E, octl = views(s)
            _insert_moves(cfg, E, carry,
                          _moves(cfg, ins, sharded, s, "insert"), M)
            _unbind_and_pool(cfg, lay, E, octl)
            _loop(0, C, lambda c, _: (_rebuild_class(
                cfg, lay, family, c, E, octl, segs), 0)[1])
            return 0

        _loop(0, S, extract)
        _loop(0, S, insert)

    souts, houts = launch(
        body, smem_in=smem_in, hbm_in=hbm, writes=tuple(names),
        smem_out=[S * Cw], hbm_out={"carry": carry_words},
        scratch=[segs_words(cfg, family, cfg.num_chunks)],
        interpret=interpret)
    new_parts = {nm: _unslab(houts[nm], S, parts[nm].shape[1])
                 for nm in names}
    return new_parts, souts[0].reshape(S, Cw)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "kind", "family", "interpret"))
def arena_defrag_txn_blocked(cfg, kind, family, mem, ctl, src, dst,
                             sizes, *, interpret: bool = False):
    """Blocked migration wave: ONE pallas_call, bit-identical to
    ``defrag.migrate_math`` and to the whole lowering.  Returns
    ``(new_mem, new_ctl)``."""
    lay = arena.layout(cfg, kind, family)
    parts = {nm: p.reshape(1, -1)
             for nm, p in arena.split(lay, mem).items()}
    new_parts, octl = _wave(cfg, 1, False, kind, family, parts,
                            ctl.reshape(1, -1), src, dst, sizes, interpret)
    flat = {nm: p.reshape(-1) for nm, p in new_parts.items()}
    return arena.join(lay, flat), octl.reshape(-1)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "num_shards", "kind", "family",
                                    "interpret"))
def sharded_arena_defrag_txn_blocked(cfg, num_shards, kind, family, mem,
                                     ctl, src, dst, sizes, *,
                                     interpret: bool = False):
    """Sharded blocked wave: ONE pallas_call — extract on every source
    shard, then insert + rebuild on every shard, so one wave covers
    in-shard compaction and cross-shard rebalance moves.  Returns
    ``(new_mem, new_ctl)``."""
    from repro.core import shards as _shards  # lazy: kernels <-> core

    scfg = _shards.shard_config(cfg, num_shards)
    slay = _shards.layout(cfg, num_shards, kind, family)
    parts = _shards.split_regions(slay, mem)
    new_parts, octl = _wave(scfg, num_shards, True, kind, family, parts,
                            ctl, src, dst, sizes, interpret)
    return _shards.join_regions(slay, new_parts), octl
