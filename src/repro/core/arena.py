"""Device-resident arena: the whole allocator state as two flat arrays.

The nested NamedTuple pytrees that PR 1 threaded through every
transaction (``RingState`` / ``VirtState`` / ``AllocCtx`` /
``ChunkMeta``) are now *views*: the state that actually lives on device
— and that ``Ouroboros.init`` returns — is an :class:`Arena` of

    ``mem``  one int32 word image holding, at fixed offsets, the heap
             proper, the free-chunk pool ring, the class queue ring (or
             the virtualized segment directory), and — for chunk
             allocators — the occupancy bitmaps, free counts, and
             chunk→class bindings;
    ``ctl``  one small int32 control block holding every counter:
             per-class ``front``/``back``, the vl ``head``/``tail``
             chunk ids, and the pool's front/back.

Word offsets are static functions of ``(HeapConfig, kind, family)``
computed here (extending the scale-free layout math of ``heap.py``),
so one ``pallas_call`` can execute an entire transaction — including
the va/vl segment walk — against ``mem``/``ctl`` without any host
round trip, and the jnp oracle operates on the *same* layout
(``tests/test_alloc_txn_parity.py`` compares arenas word for word).

The offset table is documented in DESIGN.md §7; ``describe()`` renders
it from the live layout so the doc can never drift silently.

Layouts are pure host math — cheap to inspect:

>>> from repro.core import HeapConfig
>>> from repro.core import arena
>>> cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
...                  min_page_bytes=16)
>>> lay = arena.layout(cfg, "page", "ring")
>>> lay.region("heap").offset, lay.region("heap").words
(0, 16384)
>>> [r.name for r in lay.regions]
['heap', 'pool_store', 'queue_store']
>>> lay.core_ctl_words == 4 * cfg.num_classes + 2
True
>>> lay.ctl_words == lay.core_ctl_words + lay.tele_words
True
>>> print(lay.describe().splitlines()[1])
  mem[0:16384]  heap (16384,)

The ctl block carries a fixed-offset telemetry region after the core
counters (DESIGN.md §14): per-class alloc/free/failure counts, ring
wraparounds, segment grow/shrink totals, and the overflow-walk depth
histogram.  Both kernel lowerings update it in-place inside the one
transaction ``pallas_call`` and the jnp oracle is its bit-exact
reference (``repro.obs.telemetry`` owns the update math and the host
decoder); transactions that do not account traffic (defrag waves,
compact) carry the words through unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import queues
from repro.core.chunk_alloc import ChunkMeta
from repro.core.heap import HeapConfig

KINDS = ("page", "chunk")
QUEUE_FAMILIES = ("ring", "va", "vl")

# Overflow-walk depth histogram width in the ctl telemetry region:
# bins 0..6 count lanes served at that walk attempt, bin 7 collects
# every deeper attempt (walks are bounded by num_shards - 1 anyway).
TELE_WALK_BINS = 8

# Words per SMEM window of the blocked lowering (DESIGN.md §8): the
# tiling of a 1-D int32 HBM array, so every window DMA is tile-aligned.
WINDOW_WORDS = 1024


class Arena(NamedTuple):
    """The flat device-resident allocator state (see module docstring)."""
    mem: Any  # (layout.mem_words,) int32
    ctl: Any  # (layout.ctl_words,) int32


@dataclasses.dataclass(frozen=True)
class Region:
    """One named window of ``mem``: ``[offset, offset + words)``.

    ``blocking`` records how the blocked compiled lowering
    (kernels/alloc_txn_blocked.py) reaches this region in an alloc or
    free transaction:

    - ``"window"``    the region stays in HBM (``memory_space=ANY``)
                      and the kernel reads and writes single words
                      through one ``WINDOW_WORDS``-word SMEM block,
                      DMA'd in and out as accesses move;
    - ``"untouched"`` the transaction can never write it, so the
                      blocked lowering does not even pass it to the
                      kernel.
    """
    name: str
    offset: int
    shape: Tuple[int, ...]
    blocking: str = "window"

    @property
    def words(self) -> int:
        return math.prod(self.shape)

    @property
    def end(self) -> int:
        return self.offset + self.words

    @property
    def block_shape(self) -> Optional[Tuple[int, ...]]:
        """SMEM block the blocked lowering stages this region through
        (None when the region bypasses the kernel)."""
        if self.blocking == "window":
            return (WINDOW_WORDS,)
        return None


@dataclasses.dataclass(frozen=True)
class ArenaLayout:
    """Static word layout of one (cfg, kind, family) arena."""
    cfg: HeapConfig
    kind: str
    family: str
    regions: Tuple[Region, ...]         # contiguous, in mem order
    # ctl block offsets (front/back/head/tail are C words each)
    num_classes: int
    queue_capacity: int                 # ring slots (ring) / items bound
    max_segs: int                       # directory ring width (va/vl)

    @property
    def mem_words(self) -> int:
        return self.regions[-1].end

    @property
    def core_ctl_words(self) -> int:
        """Words the transaction *state* occupies: per-class front/back/
        head/tail plus the pool's front/back.  Everything after them is
        the telemetry region."""
        return 4 * self.num_classes + 2

    @property
    def tele_words(self) -> int:
        return 4 * self.num_classes + 3 + TELE_WALK_BINS

    @property
    def ctl_words(self) -> int:
        return self.core_ctl_words + self.tele_words

    def region(self, name: str) -> Region:
        for r in self.regions:
            if r.name == name:
                return r
        raise KeyError(f"arena({self.kind},{self.family}) has no region "
                       f"{name!r}")

    def has(self, name: str) -> bool:
        return any(r.name == name for r in self.regions)

    # ctl offsets -----------------------------------------------------------
    @property
    def off_front(self) -> int:
        return 0

    @property
    def off_back(self) -> int:
        return self.num_classes

    @property
    def off_head(self) -> int:
        return 2 * self.num_classes

    @property
    def off_tail(self) -> int:
        return 3 * self.num_classes

    @property
    def off_pool_front(self) -> int:
        return 4 * self.num_classes

    @property
    def off_pool_back(self) -> int:
        return 4 * self.num_classes + 1

    # telemetry region (DESIGN.md §14; repro.obs.telemetry owns the
    # update math) — fixed offsets right after the core counters -----------
    @property
    def off_t_alloc(self) -> int:
        return self.core_ctl_words

    @property
    def off_t_free(self) -> int:
        return self.off_t_alloc + self.num_classes

    @property
    def off_t_fail(self) -> int:
        return self.off_t_free + self.num_classes

    @property
    def off_t_wrap(self) -> int:
        return self.off_t_fail + self.num_classes

    @property
    def off_t_grow(self) -> int:
        return self.off_t_wrap + self.num_classes

    @property
    def off_t_shrink(self) -> int:
        return self.off_t_grow + 1

    @property
    def off_t_pool_wrap(self) -> int:
        return self.off_t_shrink + 1

    @property
    def off_t_walk(self) -> int:
        return self.off_t_pool_wrap + 1

    def tele_fields(self) -> Tuple[Tuple[str, int, int], ...]:
        """(name, ctl offset, words) rows of the telemetry region, in
        layout order — the table DESIGN.md §14 and the host decoder
        (obs/telemetry.py) render from."""
        C = self.num_classes
        return (("t_alloc", self.off_t_alloc, C),
                ("t_free", self.off_t_free, C),
                ("t_fail", self.off_t_fail, C),
                ("t_wrap", self.off_t_wrap, C),
                ("t_grow", self.off_t_grow, 1),
                ("t_shrink", self.off_t_shrink, 1),
                ("t_pool_wrap", self.off_t_pool_wrap, 1),
                ("t_walk", self.off_t_walk, TELE_WALK_BINS))

    @property
    def wrap_capacity(self) -> int:
        """Queue positions per full turn of a class queue — the modulus
        the wraparound counter (`t_wrap`) detects crossings of.  Ring
        queues wrap at the store width; virtualized queues turn over a
        full directory of segments."""
        if self.family == "ring":
            return self.queue_capacity
        return self.max_segs * self.cfg.slots_per_segment(self.family)

    def describe(self, blocks: bool = False) -> str:
        """Human-readable offset table (DESIGN.md §7 is rendered from
        this, and a test pins the two together).  ``blocks=True``
        appends each region's blocked-lowering treatment (DESIGN.md §8;
        tests/test_arena_golden.py pins both renderings).

        The ``blocks=False`` rendering is ALSO the arena half of the
        serving snapshot fingerprint (DESIGN.md §12): a snapshotted
        arena word image restores only into an engine whose layout
        renders identically, so changing this string invalidates
        existing snapshots — loudly, which is the point."""
        lines = [f"arena(kind={self.kind}, family={self.family}): "
                 f"mem {self.mem_words} words, ctl {self.ctl_words} words"]
        for r in self.regions:
            tail = ""
            if blocks:
                bs = ("-" if r.block_shape is None
                      else "x".join(map(str, r.block_shape)))
                tail = f"  [{r.blocking}: block {bs}]"
            lines.append(f"  mem[{r.offset}:{r.end}]  {r.name} {r.shape}"
                         f"{tail}")
        C = self.num_classes
        for nm, off, w in (("front", self.off_front, C),
                           ("back", self.off_back, C),
                           ("head", self.off_head, C),
                           ("tail", self.off_tail, C),
                           ("pool_front", self.off_pool_front, 1),
                           ("pool_back", self.off_pool_back, 1)):
            lines.append(f"  ctl[{off}:{off + w}]  {nm}")
        for nm, off, w in self.tele_fields():
            lines.append(f"  ctl[{off}:{off + w}]  {nm}")
        return "\n".join(lines)


def queue_capacity(cfg: HeapConfig, kind: str) -> int:
    """Items the class queues must hold: every page of a class share
    (page kind) or every chunk id (chunk kind)."""
    if kind == "page":
        return cfg.data_chunks_per_class * cfg.pages_per_chunk(0)
    return cfg.num_chunks


@functools.lru_cache(maxsize=None)
def layout(cfg: HeapConfig, kind: str, family: str) -> ArenaLayout:
    """Compute the static arena layout for one allocator variant."""
    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}; pick from {KINDS}")
    if family not in QUEUE_FAMILIES:
        raise ValueError(
            f"unknown family {family!r}; pick from {QUEUE_FAMILIES}")
    C = cfg.num_classes
    cap = queue_capacity(cfg, kind)
    max_segs = cap // cfg.slots_per_segment(family) + 2

    # Per-region treatment under the blocked compiled lowering (see
    # Region.blocking and DESIGN.md §8).  Transactions never write the
    # heap for ring-family variants (segment traffic is what touches
    # it), and never write the pool for the plain page variant.
    heap_blk = "untouched" if family == "ring" else "window"
    pool_blk = ("untouched" if (family == "ring" and kind == "page")
                else "window")
    regions = [Region("heap", 0, (cfg.total_words,), heap_blk)]

    def add(name, shape, blocking):
        regions.append(Region(name, regions[-1].end, shape, blocking))

    add("pool_store", (1, cfg.num_chunks), pool_blk)
    if family == "ring":
        add("queue_store", (C, cap), "window")
    else:
        add("directory", (C, max_segs), "window")
    if kind == "chunk":
        add("bitmap", (cfg.num_chunks, cfg.bitmap_words_per_chunk),
            "window")
        add("free_count", (cfg.num_chunks,), "window")
        add("chunk_class", (cfg.num_chunks,), "window")

    return ArenaLayout(cfg=cfg, kind=kind, family=family,
                       regions=tuple(regions), num_classes=C,
                       queue_capacity=cap, max_segs=max_segs)


# --------------------------------------------------------------------------
# pack / unpack: arena words <-> the legacy view pytrees
# --------------------------------------------------------------------------

def _take(lay: ArenaLayout, mem, name: str):
    r = lay.region(name)
    return jax.lax.slice(mem, (r.offset,), (r.end,)).reshape(r.shape)


def tele_of(lay: ArenaLayout, ctl):
    """View of the telemetry region inside one ctl block."""
    return jax.lax.slice(ctl, (lay.core_ctl_words,), (lay.ctl_words,))


def pack(lay: ArenaLayout, q, ctx: queues.AllocCtx,
         meta: Optional[ChunkMeta], tele=None) -> Arena:
    """Flatten the view pytrees into one (mem, ctl) arena.  ``tele`` is
    the telemetry region to carry into the rebuilt ctl block — ``None``
    (a fresh arena) zeroes it; transactions pass the incoming region
    through (obs/telemetry.py then applies the counter deltas)."""
    C = lay.num_classes
    parts = [ctx.heap, ctx.pool.store.reshape(-1)]
    if lay.family == "ring":
        parts.append(q.store.reshape(-1))
        head = tail = jnp.zeros(C, jnp.int32)
    else:
        parts.append(q.directory.reshape(-1))
        head, tail = q.head, q.tail
    if lay.kind == "chunk":
        parts.append(jax.lax.bitcast_convert_type(
            meta.bitmap, jnp.int32).reshape(-1))
        parts.append(meta.free_count)
        parts.append(meta.chunk_class)
    mem = jnp.concatenate(parts)
    if tele is None:
        tele = jnp.zeros(lay.tele_words, jnp.int32)
    ctl = jnp.concatenate([q.front, q.back, head, tail,
                           ctx.pool.front, ctx.pool.back,
                           tele]).astype(jnp.int32)
    return Arena(mem=mem, ctl=ctl)


def unpack(lay: ArenaLayout, arena: Arena):
    """Rebuild the (q, ctx, meta) views from arena words.  Pure static
    slices/reshapes — XLA fuses them away, so the views cost nothing."""
    C = lay.num_classes
    mem, ctl = arena.mem, arena.ctl
    front = jax.lax.slice(ctl, (lay.off_front,), (lay.off_front + C,))
    back = jax.lax.slice(ctl, (lay.off_back,), (lay.off_back + C,))
    pool = queues.RingState(
        store=_take(lay, mem, "pool_store"),
        front=jax.lax.slice(ctl, (lay.off_pool_front,),
                            (lay.off_pool_front + 1,)),
        back=jax.lax.slice(ctl, (lay.off_pool_back,),
                           (lay.off_pool_back + 1,)))
    ctx = queues.AllocCtx(heap=heap_of(lay, arena), pool=pool)
    if lay.family == "ring":
        q = queues.RingState(store=_take(lay, mem, "queue_store"),
                             front=front, back=back)
    else:
        q = queues.VirtState(
            directory=_take(lay, mem, "directory"),
            head=jax.lax.slice(ctl, (lay.off_head,), (lay.off_head + C,)),
            tail=jax.lax.slice(ctl, (lay.off_tail,), (lay.off_tail + C,)),
            front=front, back=back)
    meta = None
    if lay.kind == "chunk":
        meta = ChunkMeta(
            bitmap=jax.lax.bitcast_convert_type(
                _take(lay, mem, "bitmap"), jnp.uint32),
            free_count=_take(lay, mem, "free_count"),
            chunk_class=_take(lay, mem, "chunk_class"))
    return q, ctx, meta


def heap_of(lay: ArenaLayout, arena: Arena):
    """View of the heap proper (the paper's word array) inside ``mem``."""
    return jax.lax.slice(arena.mem, (0,), (lay.cfg.total_words,))


def with_heap(lay: ArenaLayout, arena: Arena, heap) -> Arena:
    """Arena with the heap region replaced (offset 0, so one update)."""
    return arena._replace(
        mem=jax.lax.dynamic_update_slice(arena.mem, heap, (0,)))


# --------------------------------------------------------------------------
# region split / join: mem <-> one flat array per region
# --------------------------------------------------------------------------
#
# The blocked lowering never hands the kernel the whole ``mem`` image as
# one ref; the wrapper splits it into its regions (static slices — XLA
# fuses them away) so each region can be its own HBM operand, and joins
# the touched regions back afterwards.  Regions the transaction cannot
# write (Region.blocking == "untouched") bypass the kernel entirely and
# are reused verbatim in the join.

def split(lay: ArenaLayout, mem):
    """``mem`` as a dict of flat per-region arrays (zero-cost views)."""
    return {r.name: jax.lax.slice(mem, (r.offset,), (r.end,))
            for r in lay.regions}


def join(lay: ArenaLayout, parts) -> Any:
    """Inverse of :func:`split`: concatenate region arrays (flattened,
    in layout order) back into one ``mem`` image."""
    return jnp.concatenate([parts[r.name].reshape(-1)
                            for r in lay.regions])
