"""Blocked compiled lowering for the fused arena transactions.

``kernels/alloc_txn.arena_*_txn`` (the ``whole`` lowering) hands the
kernel the entire ``mem`` image and runs the jnp transaction math on
it.  That is exact in interpret mode, but it never lowers for a TPU:
Mosaic has no ``cumsum``, and a serving-size arena does not fit VMEM.
This module is the lowering the chip runs (``auto`` picks it on TPU;
DESIGN.md §8): the same transactions, each still ONE ``pallas_call``,
written as scalar-core code.

- Every word the kernel branches on or uses as an address is read from
  SMEM.  The control block, the lane vectors and small per-transaction
  scratch are whole SMEM operands.
- Every region the transaction touches stays in HBM
  (``memory_space=ANY``).  The kernel reaches it through a *window*:
  one 1024-word SMEM block per region, fetched and written back with
  ``pltpu.make_async_copy`` whenever an access leaves the staged block.
  1024 words is the tiling of a 1-D int32 HBM array, so every DMA is
  tile-aligned; the wrapper pads each region to a whole number of
  blocks.  Regions a transaction can never write
  (``Region.blocking == "untouched"``) bypass the kernel.
- State-free lane prep (size class, validity) runs in XLA inside the
  same jit; the kernel sees one int32 per lane: its class, or -1.
- The size classes are walked in order by an in-kernel loop.  Class
  step ``c`` performs the class-``c`` slice of the transaction: the
  lanes of class ``c`` in lane order (their in-class rank is a running
  counter), that class's queue traffic and, for chunk kinds, the whole
  class-``c`` chunk-drain loop.  Cross-class state (the chunk pool,
  heap pointer words) is mutated in class-major order, which is the
  oracle's flattened scatter order.

Every body below mirrors one oracle path (``page_alloc`` /
``chunk_alloc`` over ``queues``), and the differential harness
(tests/test_alloc_txn_parity.py) holds the jnp oracle, the whole
lowering and this lowering word-identical on randomized traces.
Interpret mode runs the same DMAs and SMEM windows as the chip, but it
accepts kernels that Mosaic refuses; tests/test_tpu_compile.py compiles
these kernels for a described v5e chip to catch that.

The sharded allocator (DESIGN.md §9) reuses every body unchanged: the
kernel loops (attempt, shard, class), and each region is one flat
array of per-shard slabs addressed through a per-shard base.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import arena
from repro.core.heap import size_to_class_device

NULL = -1
BLOCK = arena.WINDOW_WORDS   # words per SMEM window over an HBM region


def _i32(x):
    return jnp.asarray(x, jnp.int32)


def _loop(lo, hi, body, init=0):
    return jax.lax.fori_loop(_i32(lo), _i32(hi), body, init)


# --------------------------------------------------------------------------
# word access: SMEM operands and SMEM-cached windows over HBM regions
# --------------------------------------------------------------------------

def _dma(src, dst, sem):
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()


class _Window:
    """One HBM region seen through a 1024-word SMEM block.  ``tags``
    holds (staged block start or -1, dirty flag) at ``2·slot``."""
    __slots__ = ("ref", "buf", "tags", "slot", "sem", "size")

    def __init__(self, ref, buf, tags, slot, sem):
        self.ref, self.buf, self.tags = ref, buf, tags
        self.slot, self.sem = slot, sem
        self.size = ref.shape[0]

    def _blk(self, start):
        return self.ref.at[pl.ds(pl.multiple_of(start, BLOCK), BLOCK)]

    def reset(self):
        self.tags[2 * self.slot] = _i32(-1)
        self.tags[2 * self.slot + 1] = _i32(0)

    def flush(self):
        @pl.when(self.tags[2 * self.slot + 1] != 0)
        def _():
            _dma(self.buf, self._blk(self.tags[2 * self.slot]), self.sem)
            self.tags[2 * self.slot + 1] = _i32(0)

    def stage(self, a):
        """Make word ``a`` resident and return its offset in ``buf``.
        Addresses are clamped into the region, so no DMA can leave it."""
        a = jnp.clip(a, 0, self.size - 1)
        start = (a // BLOCK) * BLOCK

        @pl.when(self.tags[2 * self.slot] != start)
        def _():
            self.flush()
            _dma(self._blk(start), self.buf, self.sem)
            self.tags[2 * self.slot] = start

        return a - start


class _View:
    """A word-addressed operand at a base offset: an SMEM ref
    (``win is None``) or an HBM region window.  The sharded kernels
    address shard ``s``'s slab with ``base = s · slab_words``."""
    __slots__ = ("ref", "base", "win")

    def __init__(self, ref=None, base=0, win=None):
        self.ref, self.base, self.win = ref, base, win


def _ld(v, i):
    a = v.base + i
    if v.win is None:
        return v.ref[a]
    return v.win.buf[v.win.stage(a)]


def _st(v, i, x):
    a = v.base + i
    x = _i32(x)
    if v.win is None:
        v.ref[a] = x
        return
    w = v.win
    w.buf[w.stage(a)] = x
    w.tags[2 * w.slot + 1] = _i32(1)


def _ld_if(v, i, cond, fill=NULL):
    """``v[i] if cond else fill``; a masked lane touches no memory."""
    return jax.lax.cond(cond, lambda: _i32(_ld(v, i)),
                        lambda: _i32(fill))


def _st_if(v, i, x, cond):
    @pl.when(cond)
    def _():
        _st(v, i, x)


def _fill(v, start, words, x):
    """``v[start:start + words] = x``, one word at a time."""
    def put(j, _):
        _st(v, start + j, x)
        return 0

    _loop(0, words, put)


def _bit(k):
    return jnp.left_shift(_i32(1), k % 32)


def _flip_page(cfg, E, off, pw, sign):
    """Add ``sign`` times the page's occupancy bit to its bitmap word
    (uint32 arithmetic on int32 words, as the oracle does) and move its
    chunk's free count the other way."""
    wpc = cfg.words_per_chunk
    ch = off // wpc
    pg = (off % wpc) // pw
    a = ch * cfg.bitmap_words_per_chunk + pg // 32
    _st(E["bitmap"], a, _ld(E["bitmap"], a) + sign * _bit(pg))
    _st(E["free_count"], ch, _ld(E["free_count"], ch) - sign)


def _bind(cfg, E, ch, cls):
    """Claim chunk ``ch`` for class ``cls``: clear its bitmap row, set a
    full free count, record the binding (alloc's from-pool path)."""
    bw = cfg.bitmap_words_per_chunk
    _fill(E["bitmap"], ch * bw, bw, 0)
    _st(E["free_count"], ch, jnp.right_shift(
        _i32(cfg.max_pages_per_chunk),
        jnp.clip(cls, 0, cfg.num_classes - 1)))
    _st(E["chunk_class"], ch, cls)


# --------------------------------------------------------------------------
# lanes of one (attempt, shard) visit
# --------------------------------------------------------------------------

class _Lanes:
    """The lanes a class step sees.  ``msk[i]`` is lane ``i``'s class
    when this visit serves it, else -1.  ``put`` records a granted
    shard-local offset (globalized by ``obase``); ``off`` reads a freed
    offset back as shard-local."""
    __slots__ = ("n", "msk", "offs", "off_in", "obase")

    def __init__(self, n, msk, offs=None, off_in=None, obase=0):
        self.n, self.msk, self.offs = n, msk, offs
        self.off_in, self.obase = off_in, obase

    def member(self, i, c):
        return self.msk[i] == c

    def put(self, i, v):
        self.offs[i] = jnp.where(v >= 0, v + self.obase, v)

    def off(self, i):
        return self.off_in[i] - self.obase


def _count(L, c):
    return _loop(0, L.n, lambda i, k: k + L.member(i, c).astype(jnp.int32))


def _for_members(L, c, fn):
    """``fn(i, rank)`` for every lane of class ``c``, in lane order."""
    def lane(i, k):
        m = L.member(i, c)

        @pl.when(m)
        def _():
            fn(i, k)

        return k + m.astype(jnp.int32)

    return _loop(0, L.n, lane)


# --------------------------------------------------------------------------
# chunk-pool ring and segment grow (shared with kernels/defrag_txn.py)
# --------------------------------------------------------------------------

def _pool_pop1(octl, pool, lay, cond):
    """One predicated pool pop (``queues.pool_dequeue`` semantics: the
    slot is read at the wrapped front, masked lanes yield NULL, the
    counter advances only for active lanes)."""
    nc = lay.cfg.num_chunks
    pf = _ld(octl, lay.off_pool_front)
    v = _ld_if(pool, pf % nc, cond)
    _st(octl, lay.off_pool_front, pf + jnp.where(cond, 1, 0))
    return v


def _pool_push1(octl, pool, lay, v, cond):
    """One predicated pool push (``queues.pool_enqueue`` semantics)."""
    nc = lay.cfg.num_chunks
    pb = _ld(octl, lay.off_pool_back)
    _st_if(pool, pb % nc, v, cond)
    _st(octl, lay.off_pool_back, pb + jnp.where(cond, 1, 0))


def _va_grow(octl, pool, dirv, lay, c, spc, back, cnt):
    """Append directory segments so slots [back, back+cnt) plus the
    next insertion point are all backed (``queues._grow_counts``): pool
    pops in order, each written after the current back segment."""
    n_new = (back + cnt) // spc - back // spc
    seg_back = back // spc
    row = c * lay.max_segs

    def grow(j, _):
        chk = _pool_pop1(octl, pool, lay, True)
        _st(dirv, row + (seg_back + 1 + j) % lay.max_segs, chk)
        return 0

    _loop(0, n_new, grow)


def _vl_grow(octl, pool, heap, segs, lay, spc, tail, back, cnt):
    """Pop, terminate and chain new tail segments in the oracle's
    scatter order (all pops, all terminators, then the links).  Leaves
    ``[tail] + new_chunks`` in ``segs`` and returns the new tail."""
    wpc = lay.cfg.words_per_chunk
    W = lay.cfg.total_words
    n_new = (back + cnt) // spc - back // spc
    segs[0] = _i32(tail)

    def pop(j, _):
        segs[j + 1] = _pool_pop1(octl, pool, lay, True)
        return 0

    def term(j, _):
        w = segs[j + 1] * wpc
        _st_if(heap, w, NULL, (w >= 0) & (w < W))
        return 0

    def link(j, _):
        w = segs[j] * wpc
        _st_if(heap, w, segs[j + 1], (w >= 0) & (w < W))
        return 0

    _loop(0, n_new, pop)
    _loop(0, n_new, term)
    _loop(0, n_new, link)
    return segs[n_new]


# --------------------------------------------------------------------------
# page-kind bodies: the class-c slice of one page transaction
# --------------------------------------------------------------------------
#
# ``E`` maps region name -> _View; ``octl`` is the ctl view the class
# steps accumulate into; ``segs`` is SMEM scratch for segment chains.

def _grants(octl, lay, c, L):
    """(front, items queued, lanes granted) of class ``c``."""
    front = _ld(octl, lay.off_front + c)
    back = _ld(octl, lay.off_back + c)
    return front, back - front, jnp.clip(back - front, 0, _count(L, c))


def _grant(L, c, avail, value):
    """The first ``avail`` members of class ``c`` (in rank order) get
    ``value(rank)``."""
    def fn(i, k):
        @pl.when(k < avail)
        def _():
            L.put(i, _i32(value(k)))

    _for_members(L, c, fn)


def _page_ring_alloc(cfg, lay, c, L, E, octl, segs):
    """Class-c slice of page_alloc.alloc over the ring family: the
    first ``back - front`` members, in rank order, pop the ring."""
    q = E["queue_store"]
    cap = lay.queue_capacity
    front, avail, cnt = _grants(octl, lay, c, L)
    _grant(L, c, avail, lambda k: _ld(q, c * cap + (front + k) % cap))
    _st(octl, lay.off_front + c, front + cnt)


def _page_ring_free(cfg, lay, c, L, E, octl, segs):
    """Class-c slice of page_alloc.free over the ring family: member
    ``k`` writes ring slot ``back + k``; one back advance."""
    q = E["queue_store"]
    cap = lay.queue_capacity
    back = _ld(octl, lay.off_back + c)

    def put(i, k):
        _st(q, c * cap + (back + k) % cap, L.off(i))

    cnt = _for_members(L, c, put)
    _st(octl, lay.off_back + c, back + cnt)


def _page_va_alloc(cfg, lay, c, L, E, octl, segs):
    """Class-c slice of page_alloc.alloc over the va family: grants
    read heap segment slots through the directory row, then fully
    consumed segments go back to the pool (shrink)."""
    spc = cfg.slots_per_segment("va")
    wpc = cfg.words_per_chunk
    W = cfg.total_words
    row = c * lay.max_segs
    dirv, heap = E["directory"], E["heap"]
    front, avail, cnt = _grants(octl, lay, c, L)

    def slot(k):
        v = front + k
        word = _ld(dirv, row + (v // spc) % lay.max_segs) * wpc + v % spc
        return _ld_if(heap, word, (word >= 0) & (word < W))

    _grant(L, c, avail, slot)
    n_free = (front + cnt) // spc - front // spc
    seg_front = front // spc

    def shrink(j, _):
        freed = _ld(dirv, row + (seg_front + j) % lay.max_segs)
        _pool_push1(octl, E["pool_store"], lay, freed, True)
        return 0

    _loop(0, n_free, shrink)
    _st(octl, lay.off_front + c, front + cnt)


def _page_va_free(cfg, lay, c, L, E, octl, segs):
    """Class-c slice of page_alloc.free over the va family: segment
    grow (pool pops -> directory row), then the value writes into heap
    segment slots through the updated directory."""
    spc = cfg.slots_per_segment("va")
    wpc = cfg.words_per_chunk
    W = cfg.total_words
    row = c * lay.max_segs
    dirv, heap = E["directory"], E["heap"]
    back = _ld(octl, lay.off_back + c)
    cnt = _count(L, c)
    _va_grow(octl, E["pool_store"], dirv, lay, c, spc, back, cnt)

    def put(i, k):
        v = back + k
        word = _ld(dirv, row + (v // spc) % lay.max_segs) * wpc + v % spc
        _st_if(heap, word, L.off(i), (word >= 0) & (word < W))

    _for_members(L, c, put)
    _st(octl, lay.off_back + c, back + cnt)


def _page_vl_alloc(cfg, lay, c, L, E, octl, segs):
    """Class-c slice of page_alloc.alloc over the vl family: walk the
    next-pointer chain from the head segment, grant slots, then hand
    the consumed leading segments to the pool and advance the head."""
    spc = cfg.slots_per_segment("vl")
    wpc = cfg.words_per_chunk
    W = cfg.total_words
    heap = E["heap"]
    front, avail, cnt = _grants(octl, lay, c, L)
    n_free = (front + cnt) // spc - front // spc
    segs[0] = _ld(octl, lay.off_head + c)

    def hop(j, _):
        prev = segs[j]
        segs[j + 1] = _ld_if(heap, prev * wpc, prev >= 0)
        return 0

    _loop(0, n_free, hop)

    def slot(k):
        v = front + k
        word = segs[v // spc - front // spc] * wpc + 1 + v % spc
        return _ld_if(heap, word, (word >= 0) & (word < W))

    _grant(L, c, avail, slot)

    def shrink(j, _):
        _pool_push1(octl, E["pool_store"], lay, segs[j], True)
        return 0

    _loop(0, n_free, shrink)
    _st(octl, lay.off_head + c, segs[n_free])
    _st(octl, lay.off_front + c, front + cnt)


def _page_vl_free(cfg, lay, c, L, E, octl, segs):
    """Class-c slice of page_alloc.free over the vl family: grow (pool
    pops, terminate + link the new segments after the tail), value
    writes, tail update."""
    spc = cfg.slots_per_segment("vl")
    wpc = cfg.words_per_chunk
    W = cfg.total_words
    heap = E["heap"]
    back = _ld(octl, lay.off_back + c)
    cnt = _count(L, c)
    new_tail = _vl_grow(octl, E["pool_store"], heap, segs, lay, spc,
                        _ld(octl, lay.off_tail + c), back, cnt)

    def put(i, k):
        v = back + k
        word = segs[v // spc - back // spc] * wpc + 1 + v % spc
        _st_if(heap, word, L.off(i), (word >= 0) & (word < W))

    _for_members(L, c, put)
    _st(octl, lay.off_tail + c, new_tail)
    _st(octl, lay.off_back + c, back + cnt)


# --------------------------------------------------------------------------
# chunk-kind bodies: the per-class chunk-drain loop
# --------------------------------------------------------------------------

def _next_free(bitmap, rowb, p, ppc, want):
    """First page index ``>= p`` whose occupancy bit is clear (``ppc``
    when none is); ``p`` itself when not ``want``."""
    def body(cr):
        p, _ = cr
        word = _ld_if(bitmap, rowb + p // 32, p < ppc, 0)
        busy = (p < ppc) & ((word & _bit(p)) != 0)
        return jnp.where(busy, p + 1, p), busy

    return jax.lax.while_loop(lambda cr: cr[1], body, (p, want))[0]


def _chunk_alloc(cfg, lay, family, c, L, E, octl, segs):
    """Class-c slice of chunk_alloc.alloc: the chunk-drain loop — pop a
    chunk from the class queue (or claim a fresh one from the pool),
    claim its first free pages for the next members in rank order,
    re-enqueue it while pages remain.  Queue traffic goes through the
    class ring row (ring), the directory row (va) or the head/tail
    chain in ctl + heap (vl), one item at a time, as in the oracle's
    while loop."""
    nc = cfg.num_chunks
    wpc = cfg.words_per_chunk
    bw = cfg.bitmap_words_per_chunk
    W = cfg.total_words
    spc = cfg.slots_per_segment(family)
    max_segs = lay.max_segs
    cap = lay.queue_capacity
    row = c * (cap if family == "ring" else max_segs)

    counts_c = _count(L, c)
    pw = jnp.left_shift(_i32(cfg.page_words(0)), c)   # page words of c
    ppc = wpc // pw                                    # pages per chunk

    bitmap, fc = E["bitmap"], E["free_count"]
    pool = E["pool_store"]
    heap, q, dirv = E.get("heap"), E.get("queue_store"), E.get("directory")
    off_front, off_back = lay.off_front + c, lay.off_back + c

    def in_heap(w):
        return (w >= 0) & (w < W)

    def body(carry):
        served, fail = carry
        front = _ld(octl, off_front)
        back = _ld(octl, off_back)
        have = (back - front) > 0
        step = jnp.where(have, 1, 0)

        # -- pop one chunk from the class queue ------------------------
        if family == "ring":
            val_q = _ld(q, row + front % cap)
        elif family == "va":
            seg = _ld(dirv, row + (front // spc) % max_segs)
            word = seg * wpc + front % spc
            val_q = _ld_if(heap, word, have & in_heap(word))
            crossed = (front + 1) // spc - front // spc > 0
            _pool_push1(octl, pool, lay, seg, have & crossed)
        else:  # vl
            head = _ld(octl, lay.off_head + c)
            word = head * wpc + 1 + front % spc
            val_q = _ld_if(heap, word, have & in_heap(word))
            nxt = _ld_if(heap, head * wpc, head >= 0)
            sh = have & ((front + 1) // spc - front // spc > 0)
            _pool_push1(octl, pool, lay, head, sh)
            _st(octl, lay.off_head + c, jnp.where(sh, nxt, head))
        _st(octl, off_front, front + step)

        # -- else claim a fresh chunk from the pool --------------------
        pf = _ld(octl, lay.off_pool_front)
        has = (_ld(octl, lay.off_pool_back) - pf) > 0
        take_pool = (~have) & has
        ch_p = _ld_if(pool, pf % nc, take_pool)
        _st(octl, lay.off_pool_front, pf + jnp.where(take_pool, 1, 0))
        fail_now = (~have) & (~has)
        chunk = jnp.where(have, val_q, jnp.where(has, ch_p, NULL))

        @pl.when(take_pool)
        def _fresh():
            _bind(cfg, E, jnp.clip(jnp.where(ch_p < 0, ch_p + nc, ch_p),
                                   0, nc - 1), c)

        # -- claim the chunk's first free pages, lane by lane ----------
        # (index normalization mirrors jnp's negative-wrap + clamp
        # gather on bitmap[chunk] for chunk = -1, where t == 0)
        idxc = jnp.clip(jnp.where(chunk < 0, chunk + nc, chunk), 0, nc - 1)
        f = jnp.where(fail_now, 0, _ld(fc, idxc))
        t = jnp.minimum(counts_c - served, f)
        rowb = idxc * bw

        def lane(i, cr):
            k, p, total = cr
            m = L.member(i, c)
            want = m & (k >= served) & (k < served + t)
            p = _next_free(bitmap, rowb, p, ppc, want)
            got = want & (p < ppc)

            @pl.when(got)
            def _claim():
                a = rowb + p // 32
                _st(bitmap, a, _ld(bitmap, a) + _bit(p))
                L.put(i, chunk * wpc + p * pw)

            g = got.astype(jnp.int32)
            return k + m.astype(jnp.int32), p + g, total + g

        total = _loop(0, L.n, lane, (_i32(0), _i32(0), _i32(0)))[2]
        _st_if(fc, idxc, f - total, total > 0)

        # -- chunk still has pages -> back into the class queue --------
        leftover = (~fail_now) & (f - total > 0)
        lv = jnp.where(leftover, 1, 0)
        back = _ld(octl, off_back)
        if family == "ring":
            _st_if(q, row + back % cap, chunk, leftover)
        elif family == "va":
            _va_grow(octl, pool, dirv, lay, c, spc, back, lv)
            seg = _ld(dirv, row + (back // spc) % max_segs)
            word = seg * wpc + back % spc
            _st_if(heap, word, chunk, leftover & in_heap(word))
        else:  # vl
            tail = _ld(octl, lay.off_tail + c)
            new_tail = _vl_grow(octl, pool, heap, segs, lay, spc, tail,
                                back, lv)
            word = tail * wpc + 1 + back % spc
            _st_if(heap, word, chunk, leftover & in_heap(word))
            _st(octl, lay.off_tail + c, new_tail)
        _st(octl, off_back, back + lv)
        return served + t, fail | fail_now.astype(jnp.int32)

    jax.lax.while_loop(lambda cr: (cr[0] < counts_c) & (cr[1] == 0),
                       body, (_i32(0), _i32(0)))


def _chunk_free(cfg, lay, family, c, L, E, octl, segs, aux, nrev):
    """Chunk-kind free.  Class step 0 marks the chunks that go from
    full to non-full (``aux``, against the pre-free counts), then
    clears every freed page bit and bumps free counts, one lane at a
    time; every class step then re-enqueues its own class's revived
    chunks (ascending id, the oracle's nonzero order)."""
    C = cfg.num_classes
    nc = cfg.num_chunks
    wpc = cfg.words_per_chunk
    W = cfg.total_words
    spc = cfg.slots_per_segment(family)
    max_segs = lay.max_segs
    cap = lay.queue_capacity
    fc, cc = E["free_count"], E["chunk_class"]

    def lane_chunk(i):
        cl = L.msk[i]
        off = L.off(i)
        ch = off // wpc
        return cl, off, ch, (cl >= 0) & (ch >= 0) & (ch < nc)

    @pl.when(c == 0)
    def _clear():
        nrev[0] = _i32(0)

        def mark(i, _):
            _, _, ch, ok = lane_chunk(i)

            @pl.when(ok)
            def _():
                @pl.when(_ld(fc, ch) == 0)
                def _():
                    nrev[0] = _i32(1)
                    _st(aux, ch, 1)
            return 0

        def clear(i, _):
            cl, off, _, ok = lane_chunk(i)

            @pl.when(ok)
            def _():
                _flip_page(cfg, E, off,
                           jnp.left_shift(_i32(cfg.page_words(0)), cl % C),
                           -1)
            return 0

        _loop(0, L.n, mark)
        _loop(0, L.n, clear)

    @pl.when(nrev[0] > 0)
    def _requeue():
        def active(k):
            return (_ld(aux, k) != 0) & (_ld(cc, k) == c)

        cnt = _loop(0, nc, lambda k, a: a + active(k).astype(jnp.int32))
        back = _ld(octl, lay.off_back + c)

        def for_active(fn):
            def step(k, r):
                act = active(k)

                @pl.when(act)
                def _():
                    fn(k, r)

                return r + act.astype(jnp.int32)

            _loop(0, nc, step)

        if family == "ring":
            for_active(lambda k, r: _st(E["queue_store"],
                                        c * cap + (back + r) % cap, k))
        elif family == "va":
            dirv, heap = E["directory"], E["heap"]
            _va_grow(octl, E["pool_store"], dirv, lay, c, spc, back, cnt)

            def put(k, r):
                v = back + r
                seg = _ld(dirv, c * max_segs + (v // spc) % max_segs)
                word = seg * wpc + v % spc
                _st_if(heap, word, k, (word >= 0) & (word < W))

            for_active(put)
        else:  # vl
            heap = E["heap"]
            new_tail = _vl_grow(octl, E["pool_store"], heap, segs, lay,
                                spc, _ld(octl, lay.off_tail + c), back,
                                cnt)

            def put(k, r):
                v = back + r
                word = segs[v // spc - back // spc] * wpc + 1 + v % spc
                _st_if(heap, word, k, (word >= 0) & (word < W))

            for_active(put)
            _st(octl, lay.off_tail + c, new_tail)
        _st(octl, lay.off_back + c, back + cnt)


# --------------------------------------------------------------------------
# ctl telemetry accumulators (DESIGN.md §14; obs/telemetry.py is the
# bit-exact oracle)
# --------------------------------------------------------------------------
#
# Every telemetry word is a pure function of observable transaction
# state — lane inputs, granted offsets, core-counter before/after
# values — so the scalar per-class updates below provably equal the
# oracle's vectorized whole-transaction deltas: step c touches exactly
# class c's front/back, and the shared pool counters telescope across
# the sequential class steps (each step's post is the next step's pre).

def _tele_bump(octl, addr, delta):
    _st(octl, addr, _ld(octl, addr) + delta)


def _tele_scalars(octl, lay, c):
    """(front[c], back[c], pool_front, pool_back) — the core counters a
    class step can move, sampled around the per-class body."""
    return (_ld(octl, lay.off_front + c), _ld(octl, lay.off_back + c),
            _ld(octl, lay.off_pool_front), _ld(octl, lay.off_pool_back))


def _tele_counters(lay, octl, c, pre, post):
    """Wrap/grow/shrink/pool-wrap deltas of one class step."""
    f0, b0, pf0, pb0 = pre
    f1, b1, pf1, pb1 = post
    capw = lay.wrap_capacity
    nc = lay.cfg.num_chunks
    _tele_bump(octl, lay.off_t_wrap + c,
               (f1 // capw - f0 // capw) + (b1 // capw - b0 // capw))
    _tele_bump(octl, lay.off_t_grow, pf1 - pf0)
    _tele_bump(octl, lay.off_t_shrink, pb1 - pb0)
    _tele_bump(octl, lay.off_t_pool_wrap,
               (pf1 // nc - pf0 // nc) + (pb1 // nc - pb0 // nc))


def _tele_alloc(lay, octl, c, L, attempt):
    """Per-class alloc/failure counts and walk-depth histogram from the
    step's served lanes (every lane of the visit entered unserved)."""
    def tally(i, cr):
        m = L.member(i, c)
        ok = L.offs[i] >= 0
        return (cr[0] + (m & ok).astype(jnp.int32),
                cr[1] + (m & ~ok).astype(jnp.int32))

    served, failed = _loop(0, L.n, tally, (_i32(0), _i32(0)))
    _tele_bump(octl, lay.off_t_alloc + c, served)
    _tele_bump(octl, lay.off_t_fail + c, failed)
    nbin = jnp.minimum(attempt, arena.TELE_WALK_BINS - 1)
    _tele_bump(octl, lay.off_t_walk + nbin, served)


def _tele_free(lay, octl, c, L):
    _tele_bump(octl, lay.off_t_free + c, _count(L, c))


# --------------------------------------------------------------------------
# the launcher: regions as padded HBM slabs, one pallas_call
# --------------------------------------------------------------------------

def _slab(x):
    """(S, w) region rows -> flat (S · wp,) with wp a multiple of BLOCK
    (every window DMA stays tile-aligned); returns (flat, wp)."""
    S, w = x.shape
    wp = -(-w // BLOCK) * BLOCK
    return jnp.pad(x, ((0, 0), (0, wp - w))).reshape(S * wp), wp


def _unslab(x, S, w):
    return x.reshape(S, -1)[:, :w]


def launch(body, *, smem_in, hbm_in, writes, smem_out, hbm_out=(),
           scratch=(), interpret=False):
    """Run ``body`` as ONE ``pallas_call``.

    ``smem_in``: 1-D int32 arrays, whole SMEM operands.  ``hbm_in``:
    ``{name: flat slab}`` HBM regions; those in ``writes`` are
    input/output-aliased and updated in place.  ``smem_out``: word
    counts of SMEM outputs.  ``hbm_out``: ``{name: words}`` HBM outputs
    with no input.  ``scratch``: word counts of SMEM scratch.

    ``body(ins, wins, outs, scr)`` gets the SMEM input refs, one
    :class:`_Window` per HBM name, the SMEM output refs and the
    scratch refs.  Windows start empty and are flushed at the end.
    Returns ``(smem outputs, {name: flat slab})`` for ``writes`` and
    ``hbm_out``."""
    hbm_names = list(hbm_in)
    out_names = [nm for nm in hbm_names if nm in writes] + list(hbm_out)
    win_names = hbm_names + list(hbm_out)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    anyspec = pl.BlockSpec(memory_space=pl.ANY)
    n_si, n_h = len(smem_in), len(hbm_names)

    in_specs = [smem] * n_si + [anyspec] * n_h
    out_specs = [anyspec] * len(out_names) + [smem] * len(smem_out)
    shapes = {**{nm: hbm_in[nm].shape[0] for nm in hbm_names},
              **dict(hbm_out)}
    out_shape = ([jax.ShapeDtypeStruct((shapes[nm],), jnp.int32)
                  for nm in out_names]
                 + [jax.ShapeDtypeStruct((w,), jnp.int32)
                    for w in smem_out])
    scratch_shapes = ([pltpu.SMEM((BLOCK,), jnp.int32)] * len(win_names)
                      + [pltpu.SMEM((2 * len(win_names),), jnp.int32)]
                      + [pltpu.SMEM((max(w, 1),), jnp.int32)
                         for w in scratch]
                      + [pltpu.SemaphoreType.DMA])
    aliases = {n_si + hbm_names.index(nm): k
               for k, nm in enumerate(out_names) if nm in hbm_in}

    def kernel(*refs):
        ins = refs[:n_si]
        R = dict(zip(hbm_names, refs[n_si:n_si + n_h]))
        rest = refs[n_si + n_h:]
        O = dict(zip(out_names, rest[:len(out_names)]))
        rest = rest[len(out_names):]
        outs = rest[:len(smem_out)]
        rest = rest[len(smem_out):]
        bufs = rest[:len(win_names)]
        tags = rest[len(win_names)]
        scr = rest[len(win_names) + 1:-1]
        sem = rest[-1]
        wins = {nm: _Window(O.get(nm, R.get(nm)), bufs[k], tags, k, sem)
                for k, nm in enumerate(win_names)}
        for w in wins.values():
            w.reset()
        body(ins, wins, outs, scr)
        for w in wins.values():
            w.flush()

    res = pl.pallas_call(
        kernel, out_shape=out_shape, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch_shapes,
        input_output_aliases=aliases, interpret=interpret,
    )(*smem_in, *[hbm_in[nm] for nm in hbm_names])
    n_o = len(out_names)
    return list(res[n_o:]), dict(zip(out_names, res[:n_o]))


def _copy(src, dst, words):
    def cp(k, _):
        dst[k] = src[k]
        return 0

    _loop(0, words, cp)




# --------------------------------------------------------------------------
# the transactions: one kernel over (attempt, shard, class)
# --------------------------------------------------------------------------
#
# Region sets per transaction: `reads` enter the kernel, `writes` are
# aliased back out (everything else bypasses it).

_READS = {
    ("page", "ring", "alloc"): ("queue_store",),
    ("page", "ring", "free"): ("queue_store",),
    ("page", "va", "alloc"): ("heap", "pool_store", "directory"),
    ("page", "va", "free"): ("heap", "pool_store", "directory"),
    ("page", "vl", "alloc"): ("heap", "pool_store"),
    ("page", "vl", "free"): ("heap", "pool_store"),
    ("chunk", "ring", "alloc"): ("pool_store", "queue_store", "bitmap",
                                 "free_count", "chunk_class"),
    ("chunk", "ring", "free"): ("queue_store", "bitmap", "free_count",
                                "chunk_class"),
    ("chunk", "va", "alloc"): ("heap", "pool_store", "directory",
                               "bitmap", "free_count", "chunk_class"),
    ("chunk", "va", "free"): ("heap", "pool_store", "directory",
                              "bitmap", "free_count", "chunk_class"),
    ("chunk", "vl", "alloc"): ("heap", "pool_store", "bitmap",
                               "free_count", "chunk_class"),
    ("chunk", "vl", "free"): ("heap", "pool_store", "bitmap",
                              "free_count", "chunk_class"),
}

_WRITES = {
    ("page", "ring", "alloc"): (),
    ("page", "ring", "free"): ("queue_store",),
    ("page", "va", "alloc"): ("pool_store",),
    ("page", "va", "free"): ("heap", "directory"),
    ("page", "vl", "alloc"): ("pool_store",),
    ("page", "vl", "free"): ("heap",),
    ("chunk", "ring", "alloc"): ("queue_store", "bitmap", "free_count",
                                 "chunk_class"),
    ("chunk", "ring", "free"): ("queue_store", "bitmap", "free_count"),
    ("chunk", "va", "alloc"): ("heap", "pool_store", "directory",
                               "bitmap", "free_count", "chunk_class"),
    ("chunk", "va", "free"): ("heap", "directory", "bitmap",
                              "free_count"),
    ("chunk", "vl", "alloc"): ("heap", "pool_store", "bitmap",
                               "free_count", "chunk_class"),
    ("chunk", "vl", "free"): ("heap", "bitmap", "free_count"),
}

_PAGE_BODIES = {
    ("ring", "alloc"): _page_ring_alloc, ("ring", "free"): _page_ring_free,
    ("va", "alloc"): _page_va_alloc, ("va", "free"): _page_va_free,
    ("vl", "alloc"): _page_vl_alloc, ("vl", "free"): _page_vl_free,
}


def segs_words(cfg, family, items):
    """SMEM words for a segment chain that enqueues ``items`` items."""
    return items // cfg.slots_per_segment(family) + 2


def _txn(cfg, S, walk, sharded, kind, family, op, parts, ctl, lanes,
         interpret):
    """One alloc/free transaction over ``S`` arena slabs.

    ``parts``: ``{region: (S, words)}``; ``ctl``: ``(S, ctl_words)``;
    ``lanes``: (lane class or -1, offsets | home shard | None).  The
    kernel visits (attempt, shard, class) in order: attempt ``a``
    serves the still-unserved lanes homed on ``(home + a) % S``, the
    serial-replay schedule of DESIGN.md §9 (``S == 1``: one visit)."""
    lay = arena.layout(cfg, kind, family)
    C = cfg.num_classes
    Ws = cfg.total_words
    Cw = lay.ctl_words
    reads = _READS[(kind, family, op)]
    writes = _WRITES[(kind, family, op)]
    lc, extra = lanes
    n = lc.shape[0]
    A = walk + 1 if op == "alloc" else 1
    chunk_free = kind == "chunk" and op == "free"

    hbm, stride = {}, {}
    for nm in reads:
        hbm[nm], stride[nm] = _slab(parts[nm].reshape(S, -1))
    if chunk_free:   # full -> non-full marks, zeroed per transaction
        hbm["aux"], stride["aux"] = _slab(
            jnp.zeros((S, cfg.num_chunks), jnp.int32))
        writes = writes + ("aux",)
    smem_in = [ctl.reshape(-1).astype(jnp.int32), lc]
    if extra is not None:
        smem_in.append(extra.astype(jnp.int32))
    smem_out = [S * Cw] + ([n] if op == "alloc" else [])
    scratch = [n, segs_words(cfg, family, n), 1]

    def body(ins, wins, outs, scr):
        ctl_in, lc_ref = ins[0], ins[1]
        octl_ref = outs[0]
        offs = outs[1] if op == "alloc" else None
        msk, segs, nrev = scr
        _copy(ctl_in, octl_ref, S * Cw)
        if offs is not None:
            _fill(_View(offs), 0, n, NULL)

        def visit(a, s):
            def sel(i, _):
                cl = lc_ref[i]
                if op == "alloc":
                    ok = offs[i] < 0
                    if sharded:
                        ok &= (ins[2][i] + a) % S == s
                else:
                    ok = (ins[2][i] // Ws == s) if sharded else True
                msk[i] = jnp.where((cl >= 0) & ok, cl, -1)
                return 0

            _loop(0, n, sel)
            obase = s * Ws if sharded else 0
            L = _Lanes(n, msk, offs=offs,
                       off_in=ins[2] if op == "free" else None,
                       obase=obase)
            E = {nm: _View(base=s * stride[nm], win=wins[nm])
                 for nm in hbm}
            octl = _View(octl_ref, base=s * Cw)

            def cls_step(c, _):
                pre = _tele_scalars(octl, lay, c)
                if kind == "page":
                    _PAGE_BODIES[(family, op)](cfg, lay, c, L, E, octl,
                                               segs)
                elif op == "alloc":
                    _chunk_alloc(cfg, lay, family, c, L, E, octl, segs)
                else:
                    _chunk_free(cfg, lay, family, c, L, E, octl, segs,
                                E["aux"], nrev)
                _tele_counters(lay, octl, c, pre,
                               _tele_scalars(octl, lay, c))
                if op == "alloc":
                    _tele_alloc(lay, octl, c, L, a)
                else:
                    _tele_free(lay, octl, c, L)
                return 0

            _loop(0, C, cls_step)

        _loop(0, A, lambda a, _: (_loop(
            0, S, lambda s, __: (visit(a, s), 0)[1]), 0)[1])

    souts, houts = launch(body, smem_in=smem_in, hbm_in=hbm,
                          writes=writes, smem_out=smem_out,
                          scratch=scratch, interpret=interpret)
    new_parts = dict(parts)
    for nm in _WRITES[(kind, family, op)]:
        new_parts[nm] = _unslab(houts[nm], S, parts[nm].reshape(S, -1)
                                .shape[1])
    offsets = souts[1] if op == "alloc" else None
    return new_parts, souts[0].reshape(S, Cw), offsets


def _lane_class(cfg, sizes, mask, offsets=None):
    """Lane prep in XLA: each lane's size class, or -1 when the lane is
    masked, over-large, or (free) carries a negative offset."""
    cls = size_to_class_device(cfg, sizes)
    ok = (mask != 0) & (cls < cfg.num_classes)
    if offsets is not None:
        ok &= offsets >= 0
    return jnp.where(ok, cls, -1).astype(jnp.int32)


def _single(cfg, kind, family, op, mem, ctl, lanes, interpret):
    lay = arena.layout(cfg, kind, family)
    parts = arena.split(lay, mem)
    new_parts, octl, offs = _txn(
        cfg, 1, 0, False, kind, family, op,
        {nm: p.reshape(1, -1) for nm, p in parts.items()},
        ctl.reshape(1, -1), lanes, interpret)
    flat = {nm: p.reshape(-1) for nm, p in new_parts.items()}
    return arena.join(lay, flat), octl.reshape(-1), offs


@functools.partial(jax.jit,
                   static_argnames=("cfg", "kind", "family", "interpret"))
def arena_alloc_txn_blocked(cfg, kind, family, mem, ctl, sizes_bytes,
                            mask, *, interpret: bool = False):
    """Blocked whole-transaction alloc: ONE ``pallas_call``,
    bit-identical to ``transactions.alloc_math`` and to the whole
    lowering.  Returns ``(new_mem, new_ctl, offsets)``."""
    lc = _lane_class(cfg, sizes_bytes.astype(jnp.int32), mask)
    return _single(cfg, kind, family, "alloc", mem, ctl, (lc, None),
                   interpret)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "kind", "family", "interpret"))
def arena_free_txn_blocked(cfg, kind, family, mem, ctl, offsets_words,
                           sizes_bytes, mask, *, interpret: bool = False):
    """Blocked whole-transaction free.  Returns ``(new_mem, new_ctl)``."""
    offs = offsets_words.astype(jnp.int32)
    lc = _lane_class(cfg, sizes_bytes.astype(jnp.int32), mask, offs)
    mem2, octl, _ = _single(cfg, kind, family, "free", mem, ctl,
                            (lc, offs), interpret)
    return mem2, octl


def _sharded(cfg, num_shards, walk, kind, family, op, mem, ctl, lanes,
             interpret):
    from repro.core import shards as _shards  # lazy: kernels <-> core

    scfg = _shards.shard_config(cfg, num_shards)
    slay = _shards.layout(cfg, num_shards, kind, family)
    parts = _shards.split_regions(slay, mem)   # {name: (S, words)}
    new_parts, octl, offs = _txn(scfg, num_shards, walk, True, kind,
                                 family, op, parts, ctl, lanes, interpret)
    return _shards.join_regions(slay, new_parts), octl, offs


@functools.partial(jax.jit,
                   static_argnames=("cfg", "num_shards", "kind", "family",
                                    "walk", "interpret"))
def sharded_arena_alloc_txn_blocked(cfg, num_shards, kind, family, mem,
                                    ctl, sizes_bytes, mask, home, walk,
                                    *, interpret: bool = False):
    """Sharded blocked alloc: ONE ``pallas_call`` over the
    (attempt, shard, class) schedule, bit-identical to
    ``transactions.sharded_alloc_math`` and to the sharded whole
    lowering.  Returns ``(new_mem, new_ctl, global_offsets)``."""
    from repro.core import shards as _shards  # lazy: kernels <-> core

    scfg = _shards.shard_config(cfg, num_shards)
    lc = _lane_class(scfg, sizes_bytes.astype(jnp.int32), mask)
    return _sharded(cfg, num_shards, walk, kind, family, "alloc", mem,
                    ctl, (lc, home), interpret)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "num_shards", "kind", "family",
                                    "interpret"))
def sharded_arena_free_txn_blocked(cfg, num_shards, kind, family, mem,
                                   ctl, offsets_words, sizes_bytes,
                                   mask, *, interpret: bool = False):
    """Sharded blocked free over the (shard, class) schedule.  Returns
    ``(new_mem, new_ctl)``."""
    from repro.core import shards as _shards  # lazy: kernels <-> core

    scfg = _shards.shard_config(cfg, num_shards)
    offs = offsets_words.astype(jnp.int32)
    lc = _lane_class(scfg, sizes_bytes.astype(jnp.int32), mask, offs)
    mem2, octl, _ = _sharded(cfg, num_shards, 0, kind, family, "free",
                             mem, ctl, (lc, offs), interpret)
    return mem2, octl
