"""Unified Ouroboros allocator facade — the six paper variants.

Variant ids match the paper's driver programs (§3):

    page      — plain ring queues of pages          (fig. 1)
    chunk     — plain ring queues of chunks+bitmaps (fig. 2)
    va_page   — virtualized array queue of pages    (fig. 3)
    vl_page   — virtualized list queue of pages     (fig. 4)
    va_chunk  — virtualized array queue of chunks   (fig. 5)
    vl_chunk  — virtualized list queue of chunks    (fig. 6)

Public API (all jit-safe, functional):

    ouro = Ouroboros(cfg, "va_page", backend="pallas")
    state = ouro.init()                              # core.arena.Arena
    state, offs = ouro.alloc(state, sizes_bytes, mask)   # offs in words, -1 = fail
    state = ouro.free(state, offs, sizes_bytes, mask)
    heap  = write_pattern(state, offs, sizes_bytes, tag) # benchmark helpers
    ok    = check_pattern(state, offs, sizes_bytes, tag)

State is the flat device-resident **arena** (core/arena.py): one int32
word image ``state.mem`` (heap + pool ring + class queue ring or
segment directory + chunk bitmaps, at fixed offsets) plus one int32
control block ``state.ctl`` (every counter).  ``backend`` selects the
transaction implementation: ``"jnp"`` (default) is the pure-XLA
reference path, ``"pallas"`` executes each whole transaction —
including the va/vl segment walk — as ONE fused ``pallas_call``
(kernels/alloc_txn.arena_*_txn; interpret mode on CPU).  Both backends
are bit-identical — the jnp path is the oracle for
tests/test_alloc_txn_parity.py — and share ``init`` state, so a heap
can switch backends mid-stream (also asserted there).

With ``num_shards > 1`` the heap is partitioned into that many
independent arenas (core/shards.py, DESIGN.md §9): state becomes a
``shards.ShardedArena`` of stacked per-shard slabs, requests route to
a home shard (hashed, or caller-hinted) with a bounded overflow walk
across neighbors on exhaustion, and each transaction is STILL one
``pallas_call`` — the kernels grid the (attempt, shard) schedule.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import arena, defrag as _defrag, shards, transactions
from repro.core.heap import HeapConfig

VARIANTS = ("page", "chunk", "va_page", "vl_page", "va_chunk", "vl_chunk")
BACKENDS = ("jnp", "pallas")
LOWERINGS = ("auto", "whole", "blocked")


def _split(variant: str):
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; pick from {VARIANTS}")
    if variant in ("page", "chunk"):
        return variant, "ring"
    fam, kind = variant.split("_")
    return kind, fam


@dataclasses.dataclass(frozen=True)
class Ouroboros:
    """Facade binding a HeapConfig to one of the six paper variants.

    ``backend`` picks the transaction implementation (jnp reference
    path vs fused Pallas kernels) and — for the Pallas backend —
    ``lowering`` the kernel shape: ``"whole"`` (full-arena refs),
    ``"blocked"`` (the compiled lowering, DESIGN.md §8), or ``"auto"`` (kernels/ops picks per platform /
    REPRO_ALLOC_LOWERING).  Both lowerings are bit-identical to the
    jnp oracle and to each other (tests/test_alloc_txn_parity).

    ``num_shards > 1`` partitions the heap into independent arenas
    with overflow routing (core/shards.py, DESIGN.md §9);
    ``overflow_walk`` bounds how many neighbor shards a request may
    retry after its home shard fails (``None`` = all of them).

    Basic usage (every returned offset is a heap word offset; −1
    marks a failed lane, the GPU original's nullptr):

    >>> import jax.numpy as jnp
    >>> from repro.core import HeapConfig, Ouroboros
    >>> cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
    ...                  min_page_bytes=16)
    >>> ouro = Ouroboros(cfg, "page")
    >>> state = ouro.init()
    >>> sizes = jnp.full(4, 64, jnp.int32)      # four 64 B requests
    >>> mask = jnp.ones(4, bool)
    >>> state, offs = ouro.alloc(state, sizes, mask)
    >>> bool((offs >= 0).all())                 # all granted
    True
    >>> sorted({int(o) % 16 for o in offs})     # 64 B = 16-word aligned
    [0]
    >>> state = ouro.free(state, offs, sizes, mask)

    Sharded, with a caller-pinned home shard (the offset's owning
    shard is its global offset divided by the per-shard heap words):

    >>> ouro4 = Ouroboros(cfg, "page", num_shards=4)
    >>> st = ouro4.init()
    >>> st, offs = ouro4.alloc(st, sizes, mask, shard_hint=2)
    >>> [int(o) // ouro4.layout.shard_words for o in offs]
    [2, 2, 2, 2]
    """
    cfg: HeapConfig
    variant: str
    backend: str = "jnp"
    lowering: str = "auto"
    num_shards: int = 1
    overflow_walk: Optional[int] = None

    def __post_init__(self):
        _split(self.variant)
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; pick from {BACKENDS}")
        if self.lowering not in LOWERINGS:
            raise ValueError(
                f"unknown lowering {self.lowering!r}; pick from "
                f"{LOWERINGS}")
        if self.num_shards != 1:
            # validates divisibility + per-shard layout viability early
            shards.layout(self.cfg, self.num_shards, self.kind,
                          self.family)
            shards.resolve_walk(self.num_shards, self.overflow_walk)
        elif self.overflow_walk is not None:
            # an ignored knob is a lie: without shards there is
            # nothing to walk, so say so (symmetric with shard_hint)
            raise ValueError("overflow_walk requires num_shards > 1")

    @property
    def kind(self) -> str:
        return _split(self.variant)[0]

    @property
    def family(self) -> str:
        return _split(self.variant)[1]

    @property
    def walk(self) -> int:
        """Resolved overflow-walk length (0 when unsharded)."""
        if self.num_shards == 1:
            return 0
        return shards.resolve_walk(self.num_shards, self.overflow_walk)

    @property
    def layout(self):
        """The static word layout: an ``arena.ArenaLayout`` for a
        single arena, a ``shards.ShardLayout`` when sharded."""
        if self.num_shards == 1:
            return arena.layout(self.cfg, self.kind, self.family)
        return shards.layout(self.cfg, self.num_shards, self.kind,
                             self.family)

    def init(self):
        """Fresh allocator state (``arena.Arena``, or
        ``shards.ShardedArena`` when ``num_shards > 1``).  Backend-,
        lowering-, and routing-free: a live heap can switch any of
        them mid-stream."""
        return transactions.init(self.cfg, self.kind, self.family,
                                 self.num_shards)

    # -- transactions -------------------------------------------------------

    def alloc(self, state, sizes_bytes, mask, shard_hint=None):
        """One bulk allocation transaction.

        Returns ``(state', word_offsets)``; offset −1 marks a failed
        lane (over-large size / exhausted inventory).  ``shard_hint``
        (sharded only): ``None`` routes each lane by hash, an int or a
        per-lane int32 array pins home shards — a static int with
        ``overflow_walk=0`` additionally takes the pinned fast path,
        where the other shards bypass the kernel entirely (the shard
        analogue of ``Region.blocking == "untouched"``)."""
        if self.num_shards == 1:
            if shard_hint is not None:
                raise ValueError("shard_hint requires num_shards > 1")
            return self._alloc(state, sizes_bytes, mask)
        pinned = shards.static_hint(shard_hint)
        if pinned is not None and self.walk == 0:
            return self._alloc_pinned(state, sizes_bytes, mask,
                                      pinned % self.num_shards)
        home = shards.home_shards(sizes_bytes.shape[0], self.num_shards,
                                  shard_hint)
        return self._alloc_sharded(state, sizes_bytes, mask, home)

    def free(self, state, offsets_words, sizes_bytes, mask,
             shard_hint=None):
        """One bulk free transaction (offsets as returned by
        ``alloc``; sharded offsets are global, each owned by exactly
        one shard).  A static int ``shard_hint`` with
        ``overflow_walk=0`` frees on that shard alone (lanes whose
        offsets live elsewhere are dropped — the pinned contract)."""
        if self.num_shards == 1:
            if shard_hint is not None:
                raise ValueError("shard_hint requires num_shards > 1")
            return self._free(state, offsets_words, sizes_bytes, mask)
        pinned = shards.static_hint(shard_hint)
        if pinned is not None and self.walk == 0:
            return self._free_pinned(state, offsets_words, sizes_bytes,
                                     mask, pinned % self.num_shards)
        return self._free_sharded(state, offsets_words, sizes_bytes,
                                  mask)

    def grow(self, state, need, size_bytes: int, lanes: int, home=None):
        """Grow-to-target-lens transaction: the decode mega-step entry.

        ``need`` is a DEVICE per-slot page-need vector ``(B,)`` (how
        many new ``size_bytes`` regions each slot must be granted) —
        no host slot list, so the whole call is jit-traceable inside a
        fused decode tick.  Lane routing is
        :func:`transactions.grow_lanes` (slot-major); the bulk grant
        itself is the ordinary single transaction — still ONE
        ``pallas_call`` under ``backend="pallas"`` with either
        lowering, sharded or not.
        ``home`` (sharded only) gives per-SLOT home shards ``(B,)``;
        ``None`` homes slot ``b`` on ``b % num_shards``, the KV
        cache's routing.

        Returns ``(state', lane_offsets, lane_slot, lane_rank,
        lane_mask)`` — offset −1 marks a failed or masked lane.
        Deliberately NOT jitted here: callers embed it in their own
        jitted step (the engine donates the whole carry).

        >>> import jax.numpy as jnp
        >>> from repro.core import HeapConfig, Ouroboros
        >>> cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
        ...                  min_page_bytes=16)
        >>> ouro = Ouroboros(cfg, "page")
        >>> st = ouro.init()
        >>> need = jnp.array([2, 0, 1], jnp.int32)
        >>> st, offs, slot, rank, mask = ouro.grow(st, need, 64, lanes=4)
        >>> slot.tolist(), mask.tolist()
        ([0, 0, 2, 2], [True, True, True, False])
        >>> bool((offs[:3] >= 0).all()), int(offs[3])
        (True, -1)
        """
        lane_slot, lane_rank, lane_mask = transactions.grow_lanes(
            need, lanes)
        sizes = jnp.full(lanes, size_bytes, jnp.int32)
        if self.num_shards == 1:
            if home is not None:
                raise ValueError("home requires num_shards > 1")
            state, offs = transactions.alloc(
                self.cfg, self.kind, self.family, state, sizes,
                lane_mask, self.backend, self.lowering)
        else:
            if home is None:
                home = jnp.arange(need.shape[0], dtype=jnp.int32)
            lane_home = (jnp.asarray(home, jnp.int32)
                         % self.num_shards)[lane_slot]
            state, offs = transactions.sharded_alloc(
                self.cfg, self.num_shards, self.kind, self.family,
                state, sizes, lane_mask, lane_home, self.walk,
                self.backend, self.lowering)
        return state, offs, lane_slot, lane_rank, lane_mask

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _alloc(self, state, sizes_bytes, mask):
        return transactions.alloc(self.cfg, self.kind, self.family,
                                  state, sizes_bytes, mask, self.backend,
                                  self.lowering)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _free(self, state, offsets_words, sizes_bytes, mask):
        return transactions.free(self.cfg, self.kind, self.family, state,
                                 offsets_words, sizes_bytes, mask,
                                 self.backend, self.lowering)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _alloc_sharded(self, state, sizes_bytes, mask, home):
        return transactions.sharded_alloc(
            self.cfg, self.num_shards, self.kind, self.family, state,
            sizes_bytes, mask, home, self.walk, self.backend,
            self.lowering)

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _free_sharded(self, state, offsets_words, sizes_bytes, mask):
        return transactions.sharded_free(
            self.cfg, self.num_shards, self.kind, self.family, state,
            offsets_words, sizes_bytes, mask, self.backend,
            self.lowering)

    @functools.partial(jax.jit, static_argnums=(0, 4), donate_argnums=1)
    def _alloc_pinned(self, state, sizes_bytes, mask, s):
        """Static-hint fast path: the transaction runs the SINGLE-arena
        kernel on shard ``s``'s slab; the other shards never enter the
        kernel (static slices around it)."""
        scfg = shards.shard_config(self.cfg, self.num_shards)
        sub, local = transactions.alloc(
            scfg, self.kind, self.family, shards.take_shard(state, s),
            sizes_bytes, mask, self.backend, self.lowering)
        offs = jnp.where(local >= 0, s * scfg.total_words + local, local)
        return shards.with_shard(state, s, sub), offs

    @functools.partial(jax.jit, static_argnums=(0, 5), donate_argnums=1)
    def _free_pinned(self, state, offsets_words, sizes_bytes, mask, s):
        scfg = shards.shard_config(self.cfg, self.num_shards)
        Ws = scfg.total_words
        sel = mask & (offsets_words >= s * Ws) \
            & (offsets_words < (s + 1) * Ws)
        local = jnp.where(sel, offsets_words - s * Ws, -1)
        sub = transactions.free(
            scfg, self.kind, self.family, shards.take_shard(state, s),
            local, sizes_bytes, sel, self.backend, self.lowering)
        return shards.with_shard(state, s, sub)

    def compact(self, state):
        if self.num_shards == 1:
            return transactions.compact(self.cfg, self.kind, self.family,
                                        state)
        return transactions.sharded_compact(
            self.cfg, self.num_shards, self.kind, self.family, state)

    # -- defragmentation (core/defrag.py, DESIGN.md §10) --------------------

    def _moves(self, max_moves) -> int:
        if max_moves is None:
            max_moves = min(_defrag.DEFAULT_MAX_MOVES,
                            self.cfg.num_chunks
                            * self.cfg.max_pages_per_chunk)
        if not isinstance(max_moves, int) or max_moves < 1:
            raise ValueError(
                f"max_moves must be a positive int, got {max_moves!r}")
        return max_moves

    def defrag(self, state, max_moves=None):
        """One defragmentation wave: plan (pure jnp — pick live extents
        in the sparsest chunks, assign dense destinations), then execute
        the migration as ONE fused transaction under the configured
        backend/lowering (bit-identical across all of them).  Returns
        ``(state', forwarding)`` where ``forwarding`` is the old→new
        :class:`~repro.core.defrag.Forwarding` table callers use to
        remap held offsets (``defrag.forward_offsets``, the KV cache's
        ``apply_forwarding``).  Chunk kinds only; for page kinds the
        wave is a no-op with an empty table.  Sharded arenas defragment
        every shard in the same single wave; cross-shard moves are
        :meth:`rebalance`'s job.

        >>> import jax.numpy as jnp
        >>> from repro.core import HeapConfig, Ouroboros
        >>> cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
        ...                  min_page_bytes=16)
        >>> ouro = Ouroboros(cfg, "vl_chunk")
        >>> st = ouro.init()
        >>> sizes = jnp.full(8, 16, jnp.int32)
        >>> ones = jnp.ones(8, bool)
        >>> st, offs = ouro.alloc(st, sizes, ones)     # one dense chunk
        >>> st, fwd = ouro.defrag(st)
        >>> int((fwd.src >= 0).sum())                  # nothing to move
        0
        >>> st, offs2 = ouro.alloc(st, sizes, ones)    # heap still serves
        >>> bool((offs2 >= 0).all())
        True
        """
        M = self._moves(max_moves)
        if self.kind != "chunk":
            return state, _defrag.empty_forwarding(M)
        if self.num_shards == 1:
            return self._defrag(state, M)
        return self._defrag_sharded(state, M)

    def rebalance(self, state, max_moves=None):
        """One cross-shard rebalance wave (sharded arenas only): plan
        moves from the most- to the least-loaded shard
        (``shards.rebalance_plan_math``) and execute them through the
        same single-kernel migration wave as :meth:`defrag`.  Returns
        ``(state', forwarding)`` with GLOBAL offsets."""
        if self.num_shards == 1:
            raise ValueError("rebalance requires num_shards > 1")
        M = self._moves(max_moves)
        if self.kind != "chunk":
            return state, _defrag.empty_forwarding(M)
        return self._rebalance(state, M)

    @functools.partial(jax.jit, static_argnums=(0, 2), donate_argnums=1)
    def _defrag(self, state, max_moves):
        src, dst, sizes = transactions.defrag_plan(
            self.cfg, self.kind, self.family, state, max_moves)
        st = transactions.migrate(self.cfg, self.kind, self.family,
                                  state, src, dst, sizes, self.backend,
                                  self.lowering)
        return st, _defrag.Forwarding(src=src, dst=dst, sizes=sizes)

    @functools.partial(jax.jit, static_argnums=(0, 2), donate_argnums=1)
    def _defrag_sharded(self, state, max_moves):
        src, dst, sizes = transactions.sharded_defrag_plan(
            self.cfg, self.num_shards, self.kind, self.family, state,
            max_moves)
        st = transactions.sharded_migrate(
            self.cfg, self.num_shards, self.kind, self.family, state,
            src, dst, sizes, self.backend, self.lowering)
        return st, _defrag.Forwarding(src=src, dst=dst, sizes=sizes)

    @functools.partial(jax.jit, static_argnums=(0, 2), donate_argnums=1)
    def _rebalance(self, state, max_moves):
        src, dst, sizes = shards.rebalance_plan_math(
            self.cfg, self.num_shards, self.kind, self.family,
            state.mem, state.ctl, max_moves=max_moves)
        st = transactions.sharded_migrate(
            self.cfg, self.num_shards, self.kind, self.family, state,
            src, dst, sizes, self.backend, self.lowering)
        return st, _defrag.Forwarding(src=src, dst=dst, sizes=sizes)

    # -- fragmentation observability ----------------------------------------

    def frag_stats(self, state):
        """Fragmentation counters of ``state``: a dict with
        ``free_words``, ``largest_free_extent``, and ``frag_ratio``
        (``1 − largest_free/total_free``; 0 = one solid free block).
        Scalars for a single arena, per-shard ``(S,)`` arrays when
        ``num_shards > 1`` — the signal the serving engine surfaces
        and uses to trigger waves."""
        if self.num_shards == 1:
            free, largest = self._frag_stats(state)
        else:
            free, largest = self._frag_stats_sharded(state)
        return {"free_words": free, "largest_free_extent": largest,
                "frag_ratio": _defrag.frag_ratio(free, largest)}

    @functools.partial(jax.jit, static_argnums=0)
    def _frag_stats(self, state):
        return _defrag.frag_stats_math(self.cfg, self.kind, self.family,
                                       state.mem, state.ctl)

    @functools.partial(jax.jit, static_argnums=0)
    def _frag_stats_sharded(self, state):
        scfg = shards.shard_config(self.cfg, self.num_shards)
        pairs = [_defrag.frag_stats_math(scfg, self.kind, self.family,
                                         state.mem[s], state.ctl[s])
                 for s in range(self.num_shards)]
        return (jnp.stack([p[0] for p in pairs]),
                jnp.stack([p[1] for p in pairs]))

    def heap(self, state):
        """The heap proper (the paper's word array): for sharded state
        the per-shard heap regions concatenated in shard order, so
        GLOBAL word offsets index it directly."""
        if self.num_shards == 1:
            return arena.heap_of(self.layout, state)
        return shards.heap_of(self.layout, state)

    def _with_heap(self, state, heap):
        if self.num_shards == 1:
            return arena.with_heap(self.layout, state, heap)
        return shards.with_heap(self.layout, state, heap)

    # -- benchmark data path (paper §3: "writing some data, checking that
    #    the data is correct when read back") -------------------------------
    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def write_pattern(self, state, offsets_words, sizes_bytes, tag):
        heap = write_words(self.cfg, self.heap(state), offsets_words,
                           sizes_bytes, tag)
        return self._with_heap(state, heap)

    @functools.partial(jax.jit, static_argnums=0)
    def check_pattern(self, state, offsets_words, sizes_bytes, tag):
        return check_words(self.cfg, self.heap(state), offsets_words,
                           sizes_bytes, tag)


def _word_grid(cfg: HeapConfig, offsets_words, sizes_bytes):
    n = offsets_words.shape[0]
    maxw = cfg.words_per_chunk  # largest page
    nw = jnp.maximum(sizes_bytes // 4, 1).astype(jnp.int32)
    j = jnp.arange(maxw, dtype=jnp.int32)[None, :]
    ok = (j < nw[:, None]) & (offsets_words[:, None] >= 0)
    words = offsets_words[:, None] + j
    return words, ok


def write_words(cfg, heap, offsets_words, sizes_bytes, tag):
    """Fill each allocation with ``tag[i]`` (one distinct word per alloc).

    ``heap`` must be the heap *view* (``cfg.total_words`` long), never
    the whole arena image: dropped lanes index one-past-the-end."""
    words, ok = _word_grid(cfg, offsets_words, sizes_bytes)
    vals = jnp.broadcast_to(tag[:, None], words.shape)
    return heap.at[jnp.where(ok, words, heap.shape[0])].set(
        vals, mode="drop")


def check_words(cfg, heap, offsets_words, sizes_bytes, tag):
    """Per-allocation bool: every word still holds its tag (detects
    overlapping allocations — the paper's correctness check)."""
    words, ok = _word_grid(cfg, offsets_words, sizes_bytes)
    got = heap.at[words].get(mode="fill", fill_value=-1)
    good = jnp.where(ok, got == tag[:, None], True)
    return good.all(axis=1) & (offsets_words >= 0)
