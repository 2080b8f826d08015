"""Sharded multi-arena allocator: N independent arenas + overflow routing.

The single device-resident arena (core/arena.py) funnels every request
through one set of rings, directories, and bitmaps.  That is the right
shape for one kernel, but the paper's headline claim is throughput
under *massive concurrency* — and the serving north star ("heavy
traffic from millions of users", ROADMAP) needs the allocator to scale
horizontally.  This module partitions the heap into ``num_shards``
independent arenas:

    ``ShardedArena.mem``  (S, shard_mem_words) — shard ``s``'s word
                          image is row ``s``, laid out by the SAME
                          :class:`~repro.core.arena.ArenaLayout` as a
                          single arena of ``total_bytes / S`` (so
                          ``arena.split``/``join`` and every region
                          offset work per shard unchanged);
    ``ShardedArena.ctl``  (S, shard_ctl_words) — one control block per
                          shard.

Routing (DESIGN.md §9): every request lane gets a **home shard** —
``hash(lane) % S`` by default, or an explicit ``shard_hint`` from the
caller (the KV cache pins each sequence's pages this way) — and a
transaction serves lanes **attempt-major, shard-minor**: attempt 0
visits each shard with its home lanes; lanes a shard could not serve
retry on ``home + 1, home + 2, …`` (mod S) up to a bounded **overflow
walk** (default: all S−1 neighbors, so a request only fails once every
shard is exhausted).  Offsets returned to callers are GLOBAL heap word
offsets: ``global = shard * shard_words + local``.

The replay order is the correctness contract: the jnp oracle
(``transactions.sharded_alloc_math``) literally replays the wavefront
through the per-shard single-arena math in that order, and both Pallas
lowerings run the SAME schedule inside one ``pallas_call``
(kernels/alloc_txn.sharded_* and alloc_txn_blocked.sharded_*), so all
implementations are bit-identical to a serial single-shard oracle
replay (tests/test_alloc_txn_parity.py).

With a *static* ``shard_hint`` and ``overflow_walk=0`` the transaction
touches exactly one shard, and the other S−1 rows bypass the kernel
entirely (static slices around the single-arena kernel) — the shard
analogue of ``Region.blocking == "untouched"``.
"""
from __future__ import annotations

import dataclasses
import functools
import numbers
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import arena
from repro.core.heap import HeapConfig

# Knuth's multiplicative hash constant (2^32 / golden ratio): cheap,
# well-mixing lane -> home-shard map that both the oracle and the
# kernels receive as a precomputed lane vector.
_HASH_MULT = 2654435761


class ShardedArena(NamedTuple):
    """Stacked per-shard allocator state (see module docstring).

    >>> from repro.core import HeapConfig, shards
    >>> cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
    ...                  min_page_bytes=16)
    >>> st = shards.init(cfg, 4, "page", "ring")
    >>> st.num_shards, st.mem.ndim, st.ctl.ndim
    (4, 2, 2)
    """
    mem: Any  # (num_shards, shard mem_words) int32
    ctl: Any  # (num_shards, shard ctl_words) int32

    @property
    def num_shards(self) -> int:
        return self.mem.shape[0]


def shard_config(cfg: HeapConfig, num_shards: int) -> HeapConfig:
    """The per-shard HeapConfig: same chunk/page geometry, 1/S of the
    bytes.  Shard boundaries are chunk boundaries by construction."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if cfg.num_chunks % num_shards:
        raise ValueError(
            f"num_shards={num_shards} must divide num_chunks="
            f"{cfg.num_chunks} (shards split the heap chunk-wise)")
    return dataclasses.replace(
        cfg, total_bytes=cfg.total_bytes // num_shards)


@dataclasses.dataclass(frozen=True)
class ShardLayout:
    """Static layout of a sharded arena: ``num_shards`` copies of one
    per-shard :class:`~repro.core.arena.ArenaLayout` (``self.shard``),
    plus the global-offset convention.  DESIGN.md §9 is rendered from
    ``describe()`` (tests/golden/shard_layout.txt pins it)."""
    cfg: HeapConfig            # the GLOBAL heap config
    num_shards: int
    kind: str
    family: str

    @property
    def shard_cfg(self) -> HeapConfig:
        return shard_config(self.cfg, self.num_shards)

    @property
    def shard(self) -> arena.ArenaLayout:
        """The per-shard arena layout (every offset is shard-local)."""
        return arena.layout(self.shard_cfg, self.kind, self.family)

    @property
    def shard_words(self) -> int:
        """Heap words per shard: global offset = s·shard_words + local."""
        return self.shard_cfg.total_words

    @property
    def mem_words(self) -> int:
        return self.shard.mem_words

    @property
    def ctl_words(self) -> int:
        return self.shard.ctl_words

    def describe(self, blocks: bool = False) -> str:
        """Human-readable shard table + the per-shard §7/§8 rendering
        (DESIGN.md §9 embeds this; tests pin doc and code together).
        Like ``ArenaLayout.describe``, the ``blocks=False`` rendering
        doubles as the serving snapshot fingerprint's layout field
        (DESIGN.md §12) — changing it invalidates existing sharded
        snapshots loudly."""
        S = self.num_shards
        lines = [
            f"sharded arena(kind={self.kind}, family={self.family}, "
            f"num_shards={S}): mem {S}x{self.mem_words} words, "
            f"ctl {S}x{self.ctl_words} words",
            f"  global heap offset = shard * {self.shard_words} + local; "
            f"home = hash(lane) % {S} or shard_hint; overflow walk "
            f"retries home+1..home+{S - 1} (mod {S})",
        ]
        lines += ["  " + ln
                  for ln in self.shard.describe(blocks=blocks).splitlines()]
        return "\n".join(lines)


@functools.lru_cache(maxsize=None)
def layout(cfg: HeapConfig, num_shards: int, kind: str,
           family: str) -> ShardLayout:
    shard_config(cfg, num_shards)  # validate divisibility early
    arena.layout(shard_config(cfg, num_shards), kind, family)
    return ShardLayout(cfg=cfg, num_shards=num_shards, kind=kind,
                       family=family)


def resolve_walk(num_shards: int, overflow_walk: Optional[int]) -> int:
    """Concrete overflow-walk length: how many NEIGHBOR shards a lane
    may retry after its home shard fails.  ``None`` = all S−1 neighbors
    (a request fails only when every shard is exhausted)."""
    if overflow_walk is None:
        return num_shards - 1
    if not isinstance(overflow_walk, int) or overflow_walk < 0:
        raise ValueError(
            f"overflow_walk must be None or an int >= 0, got "
            f"{overflow_walk!r}")
    return min(overflow_walk, num_shards - 1)


def static_hint(shard_hint) -> Optional[int]:
    """``shard_hint`` as a static Python int when it is one (incl.
    numpy integer scalars), else None — the predicate deciding whether
    the pinned fast path can apply."""
    if shard_hint is None or isinstance(shard_hint, bool):
        return None
    if isinstance(shard_hint, numbers.Integral):
        return int(shard_hint)
    return None


def home_shards(n: int, num_shards: int, shard_hint=None):
    """Per-lane home-shard vector, shared verbatim by the oracle and
    both kernel lowerings (so routing can never diverge between them).

    ``shard_hint=None`` hashes the lane index; an integer pins every
    lane to one shard; an array gives per-lane homes (e.g. the KV
    cache routing each sequence slot to ``slot % S``)."""
    if shard_hint is None:
        i = jnp.arange(n, dtype=jnp.uint32)
        h = i * jnp.uint32(_HASH_MULT)
        h = h ^ (h >> jnp.uint32(16))
        return (h % jnp.uint32(num_shards)).astype(jnp.int32)
    pinned = static_hint(shard_hint)
    if pinned is not None:
        return jnp.full(n, pinned % num_shards, jnp.int32)
    hint = jnp.asarray(shard_hint, jnp.int32)
    if hint.shape != (n,):
        raise ValueError(
            f"shard_hint array must have shape ({n},), got {hint.shape}")
    return hint % num_shards


def init(cfg: HeapConfig, num_shards: int, kind: str,
         family: str) -> ShardedArena:
    """S identical fresh shards (each shard inits exactly like a
    single arena of the per-shard config — backend- and lowering-free,
    like ``transactions.init``)."""
    from repro.core import transactions  # lazy: shards <-> transactions
    sub = transactions.init(shard_config(cfg, num_shards), kind, family)
    return ShardedArena(mem=jnp.tile(sub.mem[None], (num_shards, 1)),
                        ctl=jnp.tile(sub.ctl[None], (num_shards, 1)))


# --------------------------------------------------------------------------
# views: global heap, per-shard slabs, per-region stacks
# --------------------------------------------------------------------------

def heap_of(slay: ShardLayout, state: ShardedArena):
    """The GLOBAL heap view (S·shard_words,): per-shard heap regions
    concatenated in shard order, so global word offsets index it
    directly (write_pattern/check_pattern run on this view)."""
    W = slay.shard_words
    return jax.lax.slice(state.mem, (0, 0),
                         (slay.num_shards, W)).reshape(-1)


def with_heap(slay: ShardLayout, state: ShardedArena,
              heap) -> ShardedArena:
    """State with the global heap view replaced (inverse of heap_of)."""
    W = slay.shard_words
    return state._replace(mem=jax.lax.dynamic_update_slice(
        state.mem, heap.reshape(slay.num_shards, W), (0, 0)))


def shard_of(slay: ShardLayout, offsets_words):
    """Owning shard of each global offset (−1 for failed lanes)."""
    return jnp.where(offsets_words >= 0,
                     offsets_words // slay.shard_words, -1)


def take_shard(state: ShardedArena, s: int) -> arena.Arena:
    """Shard ``s``'s slab as a plain single-arena state (static slice:
    the pinned fast path runs the single-arena kernel on exactly this,
    and the other shards never enter the kernel)."""
    return arena.Arena(mem=state.mem[s], ctl=state.ctl[s])


def with_shard(state: ShardedArena, s: int,
               sub: arena.Arena) -> ShardedArena:
    """Inverse of :func:`take_shard`: replace one shard's slab."""
    return ShardedArena(mem=state.mem.at[s].set(sub.mem),
                        ctl=state.ctl.at[s].set(sub.ctl))


# --------------------------------------------------------------------------
# cross-shard rebalancing: plan moves from the most- to the least-loaded
# --------------------------------------------------------------------------

def shard_live_words(cfg: HeapConfig, num_shards: int, kind: str,
                     family: str, mem, ctl):
    """(S,) live heap words per shard (bound chunks' occupied pages) —
    the load metric the rebalance plan and the engine's imbalance
    trigger share.  Zero for page kinds (no binding to rebalance)."""
    import jax.numpy as jnp
    scfg = shard_config(cfg, num_shards)
    if kind != "chunk":
        return jnp.zeros(num_shards, jnp.int32)
    lay = arena.layout(scfg, kind, family)
    C = scfg.num_classes
    out = []
    for s in range(num_shards):
        _, _, meta = arena.unpack(lay, arena.Arena(mem[s], ctl[s]))
        cc = jnp.clip(meta.chunk_class, 0, C - 1)
        ppc = jnp.right_shift(scfg.max_pages_per_chunk, cc)
        pw = jnp.left_shift(scfg.page_words(0), cc)
        live = jnp.where(meta.chunk_class >= 0,
                         (ppc - meta.free_count) * pw, 0)
        out.append(jnp.sum(live))
    return jnp.stack(out).astype(jnp.int32)


def rebalance_plan_math(cfg: HeapConfig, num_shards: int, kind: str,
                        family: str, mem, ctl, *, max_moves: int):
    """Cross-shard relocation plan (DESIGN.md §10): move live extents
    from the most-loaded shard's **sparsest** chunks into free slots of
    the least-loaded shard's **densest** bound chunks, class by class,
    until the load gap would close (half the difference) or the table
    fills.  Returns ``(src, dst, sizes)`` GLOBAL word offsets, −1
    padded — the same forwarding-table format as the in-shard plan,
    executed by the same ``transactions.sharded_migrate`` wave (which
    rebuilds every shard, so the donor retires its emptied chunks)."""
    import jax
    import jax.numpy as jnp
    from repro.core import defrag as _defrag

    if kind != "chunk":
        f = _defrag.empty_forwarding(max_moves)
        return f.src, f.dst, f.sizes
    scfg = shard_config(cfg, num_shards)
    lay = arena.layout(scfg, kind, family)
    C = scfg.num_classes
    nc = scfg.num_chunks
    wpc = scfg.words_per_chunk
    Ws = scfg.total_words
    maxbits = scfg.bitmap_words_per_chunk * 32
    ids = jnp.arange(nc, dtype=jnp.int32)
    bitpos = jnp.arange(maxbits, dtype=jnp.int32)

    live_w = shard_live_words(cfg, num_shards, kind, family, mem, ctl)
    donor = jnp.argmax(live_w).astype(jnp.int32)
    recv = jnp.argmin(live_w).astype(jnp.int32)
    budget_words = jnp.maximum(
        (jnp.max(live_w) - jnp.min(live_w)) // 2, 0)

    def views_of(s):
        _, ctx, meta = arena.unpack(lay, arena.Arena(
            jax.lax.dynamic_index_in_dim(mem, s, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(ctl, s, 0, keepdims=False)))
        return ctx, meta

    (_, dm), (rctx, rm) = views_of(donor), views_of(recv)
    d_occ = _defrag._occupancy_bits(dm.bitmap)
    r_occ = _defrag._occupancy_bits(rm.bitmap)
    # the receiver accepts moves into free slots of its bound chunks
    # AND into chunks sitting in its pool (the execute step claims
    # those on insert, exactly like alloc's from-pool path)
    r_pool = _defrag._pool_members(scfg, rctx.pool)

    src = jnp.full(max_moves, -1, jnp.int32)
    dst = jnp.full(max_moves, -1, jnp.int32)
    sz = jnp.zeros(max_moves, jnp.int32)
    base = jnp.int32(0)
    k = jnp.arange(max_moves, dtype=jnp.int32)
    for c in range(C):
        ppc = scfg.pages_per_chunk(c)
        pw = scfg.page_words(c)
        in_range = bitpos[None, :] < ppc
        d_bound = dm.chunk_class == c
        r_bound = rm.chunk_class == c
        d_live = jnp.where(d_bound, ppc - dm.free_count, 0)
        r_live = jnp.where(r_bound, ppc - rm.free_count, 0)
        # donor pages from its sparsest chunks first (so they empty and
        # retire in this wave); receiver slots densest-bound-first,
        # then pool chunks (claimed at insert) in id order
        d_key = jnp.where(d_bound, d_live * nc + ids,
                          (ppc + 1) * nc + ids)
        r_key = jnp.where(r_bound, (ppc - r_live) * nc + ids,
                          jnp.where(r_pool, (ppc + 1) * nc + ids,
                                    (ppc + 2) * nc + ids))
        d_order = jnp.argsort(d_key)
        r_order = jnp.argsort(r_key)
        src_bits = d_occ & d_bound[:, None] & in_range
        dst_bits = (((~r_occ) & r_bound[:, None])
                    | r_pool[:, None]) & in_range
        avail = jnp.minimum(jnp.sum(src_bits.astype(jnp.int32)),
                            jnp.sum(dst_bits.astype(jnp.int32)))
        budget = jnp.clip(jnp.minimum(budget_words // pw, avail),
                          0, max_moves - base)
        off_of = ids[:, None] * wpc + bitpos[None, :] * pw
        s_off, cnt = _defrag._take_bits(src_bits, d_order, budget,
                                        off_of, max_moves)
        d_off, _ = _defrag._take_bits(dst_bits, r_order, budget,
                                      off_of, max_moves)
        pos = jnp.where(k < cnt, base + k, max_moves)
        src = src.at[pos].set(s_off + donor * Ws, mode="drop")
        dst = dst.at[pos].set(d_off + recv * Ws, mode="drop")
        sz = sz.at[pos].set(scfg.page_bytes(c), mode="drop")
        base = base + cnt
        budget_words = budget_words - cnt * pw
        # a pool chunk claimed by this class must not be offered to a
        # later class in the same wave (one chunk, one page size)
        used = jnp.zeros(nc + 1, bool).at[
            jnp.where((k < cnt) & (d_off >= 0), d_off // wpc, nc)].set(
            True, mode="drop")
        r_pool = r_pool & ~used[:nc]
    # a shard never rebalances onto itself (equal loads → zero budget,
    # but pin it structurally too)
    noop = donor == recv
    src = jnp.where(noop, -1, src)
    dst = jnp.where(noop, -1, dst)
    sz = jnp.where(noop, 0, sz)
    return src, dst, sz


def split_regions(slay: ShardLayout, mem):
    """``mem`` (S, mem_words) as {region: (S, region words)} stacked
    per-shard views (zero-cost static slices — the sharded blocked
    lowering's plumbing, mirroring ``arena.split``)."""
    S = slay.num_shards
    return {r.name: jax.lax.slice(mem, (0, r.offset), (S, r.end))
            for r in slay.shard.regions}


def join_regions(slay: ShardLayout, parts):
    """Inverse of :func:`split_regions`."""
    S = slay.num_shards
    return jnp.concatenate([parts[r.name].reshape(S, -1)
                            for r in slay.shard.regions], axis=1)
