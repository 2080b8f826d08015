"""Unified allocator transactions over the device-resident arena.

This is the single dispatcher the ISSUE calls for: every variant is a
``(kind, family)`` pair — ``kind`` picks the item algorithm (page
inventory vs chunk bitmap claim, the former ``page_alloc``/
``chunk_alloc`` split) and ``family`` the queue machinery (ring / va /
vl) — and every transaction runs against one :class:`arena.Arena`.

Two execution paths share one body:

``*_math``   the pure transaction math ``(mem, ctl, …) → (mem', ctl', …)``.
             It unpacks the arena into the legacy view pytrees, runs the
             jnp reference algorithms (``page_alloc``/``chunk_alloc``
             with their internal backend pinned to ``"jnp"``), and packs
             the result.  Views are static slices — XLA sees one fused
             program over two flat arrays.

``alloc``/``free``   the public dispatcher.  ``backend="jnp"`` calls the
             math directly (the oracle); ``backend="pallas"`` hands the
             transaction to ONE ``pallas_call`` executing it whole —
             masked rank, inventory grant, ring pop/push, chunk-bitmap
             claim, and the va/vl segment walk with its grow/shrink
             against the chunk pool — under the ``lowering`` the
             dispatcher stitches in (kernels/ops.resolve_lowering):

             ``whole``    the kernel body IS this module's math over
                          full ``mem``/``ctl`` refs (kernels/alloc_txn)
                          — parity with the oracle is structural;
             ``blocked``  the compiled lowering (kernels/
                          alloc_txn_blocked, DESIGN.md §8): the same
                          math as scalar-core per-class bodies over
                          SMEM windows of the arena regions — parity
                          is enforced word for word by the three-way
                          differential matrix.

             tests/test_alloc_txn_parity.py holds all implementations
             bit-identical and asserts the one-kernel property on the
             jaxpr for both lowerings.

The ``sharded_*`` entry points are the same contract over a
:class:`~repro.core.shards.ShardedArena` (num_shards independent
arenas, home-shard routing, bounded overflow walk — DESIGN.md §9):
``sharded_alloc_math``/``sharded_free_math`` are the serial
single-shard replay oracle, and the Pallas backends grid that exact
schedule into ONE pallas_call per transaction.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core import arena, chunk_alloc, defrag, page_alloc, shards
from repro.core.heap import HeapConfig
from repro.core.page_alloc import AllocState


def _impl(kind: str):
    return page_alloc if kind == "page" else chunk_alloc


def _views(cfg: HeapConfig, kind: str, family: str, mem, ctl):
    lay = arena.layout(cfg, kind, family)
    q, ctx, meta = arena.unpack(lay, arena.Arena(mem, ctl))
    return lay, AllocState(q=q, ctx=ctx, meta=meta)


def init(cfg: HeapConfig, kind: str, family: str, num_shards: int = 1):
    """Build the arena (backend-free, so a live heap can switch
    backends mid-stream — asserted by the parity tests).  With
    ``num_shards > 1`` the state is a :class:`shards.ShardedArena` of
    ``num_shards`` identical fresh per-shard arenas."""
    if num_shards != 1:
        return shards.init(cfg, num_shards, kind, family)
    lay = arena.layout(cfg, kind, family)
    st = _impl(kind).init(cfg, family)
    return arena.pack(lay, st.q, st.ctx, st.meta)


# ---- pure transaction math (shared by both backends) ----------------------
#
# Telemetry (DESIGN.md §14): both transactions thread the incoming ctl
# telemetry region through ``arena.pack`` and advance it via the
# obs/telemetry.py delta math — the whole-lowering kernel body calls
# these functions directly, so its telemetry is structurally identical;
# the blocked lowering reproduces the same deltas per class step.

def alloc_math(cfg: HeapConfig, kind: str, family: str, mem, ctl,
               sizes_bytes, mask, attempt=0) -> Tuple:
    """``attempt`` is the overflow-walk attempt this call serves — 0
    for single-arena traffic; the sharded schedule passes its attempt
    index (traced) so the walk-depth histogram attributes lanes to the
    attempt that actually served them."""
    from repro.obs import telemetry
    lay, st = _views(cfg, kind, family, mem, ctl)
    st, offs = _impl(kind).alloc(cfg, family, st, sizes_bytes, mask, "jnp")
    new = arena.pack(lay, st.q, st.ctx, st.meta,
                     tele=arena.tele_of(lay, ctl))
    new_ctl = telemetry.alloc_update(lay, ctl, new.ctl, sizes_bytes,
                                     mask, offs, attempt)
    return new.mem, new_ctl, offs


def free_math(cfg: HeapConfig, kind: str, family: str, mem, ctl,
              offsets_words, sizes_bytes, mask) -> Tuple:
    from repro.obs import telemetry
    lay, st = _views(cfg, kind, family, mem, ctl)
    st = _impl(kind).free(cfg, family, st, offsets_words, sizes_bytes,
                          mask, "jnp")
    new = arena.pack(lay, st.q, st.ctx, st.meta,
                     tele=arena.tele_of(lay, ctl))
    new_ctl = telemetry.free_update(lay, ctl, new.ctl, sizes_bytes,
                                    mask, offsets_words)
    return new.mem, new_ctl


# ---- grow-to-target-lens lane routing (decode mega-step entry) ------------

def grow_lanes(need, lanes: int):
    """Expand a DEVICE per-slot page-need vector into allocation lanes.

    The decode mega-step computes ``need[b]`` (how many new pages slot
    ``b`` must be granted this tick) from device-resident sequence
    lengths — no host slot list exists.  This routine turns that vector
    into the lane layout every alloc transaction consumes: lane ``j``
    carries slot ``slot[j]``'s ``rank[j]``-th new page, slots packed in
    slot order, and
    ``mask[j]`` marks live lanes.  Pure jnp, shared verbatim by both
    backends and both Pallas lowerings, so lane routing can never
    diverge between them.  Lanes beyond ``sum(need)`` are masked;
    demand beyond ``lanes`` is silently truncated — callers detect the
    shortfall by comparing granted counts against ``need``.

    >>> import jax.numpy as jnp
    >>> from repro.core.transactions import grow_lanes
    >>> slot, rank, mask = grow_lanes(jnp.array([2, 0, 1]), lanes=4)
    >>> slot.tolist(), rank.tolist(), mask.tolist()
    ([0, 0, 2, 2], [0, 1, 0, 0], [True, True, True, False])
    """
    need = need.astype(jnp.int32)
    B = need.shape[0]
    cum = jnp.cumsum(need)
    j = jnp.arange(lanes, dtype=jnp.int32)
    mask = j < cum[-1]
    slot = jnp.minimum(
        jnp.searchsorted(cum, j, side="right").astype(jnp.int32), B - 1)
    rank = jnp.where(mask, j - (cum[slot] - need[slot]), 0)
    return slot, rank, mask


# ---- public dispatcher ----------------------------------------------------

BACKENDS = ("jnp", "pallas")


def _check_backend(backend: str) -> None:
    # Fail loudly here too, not only in the Ouroboros facade: a typo
    # like "palas" must never silently fall through to the jnp branch
    # for callers that reach the dispatcher directly.
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; pick from {BACKENDS}")


def alloc(cfg: HeapConfig, kind: str, family: str, state: arena.Arena,
          sizes_bytes, mask, backend: str = "jnp",
          lowering: str = "auto"):
    """One bulk allocation transaction.  Returns (arena', word_offsets);
    offset −1 marks a failed lane (over-large size / exhausted
    inventory), matching the GPU original's nullptr.  ``lowering``
    picks the Pallas kernel shape (whole-arena refs vs the blocked
    compiled lowering — kernels/ops.resolve_lowering).

    The dispatcher is the layer below the ``Ouroboros`` facade — same
    semantics, explicit (kind, family):

    >>> import jax.numpy as jnp
    >>> from repro.core import HeapConfig, transactions
    >>> cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
    ...                  min_page_bytes=16)
    >>> st = transactions.init(cfg, "page", "ring")
    >>> sizes = jnp.full(2, 64, jnp.int32)
    >>> st, offs = transactions.alloc(cfg, "page", "ring", st, sizes,
    ...                               jnp.ones(2, bool))
    >>> bool((offs >= 0).all())
    True
    """
    _check_backend(backend)
    if backend == "pallas":
        from repro.kernels import ops as kops
        mem, ctl, offs = kops.arena_alloc_txn(cfg, kind, family,
                                              state.mem, state.ctl,
                                              sizes_bytes, mask,
                                              lowering=lowering)
    else:
        mem, ctl, offs = alloc_math(cfg, kind, family, state.mem,
                                    state.ctl, sizes_bytes, mask)
    return arena.Arena(mem=mem, ctl=ctl), offs


def free(cfg: HeapConfig, kind: str, family: str, state: arena.Arena,
         offsets_words, sizes_bytes, mask, backend: str = "jnp",
         lowering: str = "auto"):
    """One bulk free transaction (inverse of :func:`alloc`; masked or
    negative-offset lanes are no-ops).  Freed pages become grantable
    again immediately:

    >>> import jax.numpy as jnp
    >>> from repro.core import HeapConfig, transactions
    >>> cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
    ...                  min_page_bytes=16)
    >>> st = transactions.init(cfg, "page", "ring")
    >>> sizes = jnp.full(2, 64, jnp.int32)
    >>> ones = jnp.ones(2, bool)
    >>> st, offs = transactions.alloc(cfg, "page", "ring", st, sizes,
    ...                               ones)
    >>> st = transactions.free(cfg, "page", "ring", st, offs, sizes,
    ...                        ones)
    >>> st, offs2 = transactions.alloc(cfg, "page", "ring", st, sizes,
    ...                                ones)
    >>> bool((offs2 >= 0).all())
    True
    """
    _check_backend(backend)
    if backend == "pallas":
        from repro.kernels import ops as kops
        mem, ctl = kops.arena_free_txn(cfg, kind, family, state.mem,
                                       state.ctl, offsets_words,
                                       sizes_bytes, mask,
                                       lowering=lowering)
    else:
        mem, ctl = free_math(cfg, kind, family, state.mem, state.ctl,
                             offsets_words, sizes_bytes, mask)
    return arena.Arena(mem=mem, ctl=ctl)


def compact(cfg: HeapConfig, kind: str, family: str,
            state: arena.Arena) -> arena.Arena:
    """Host-triggered chunk-rebind pass (chunk kinds only; DESIGN.md
    §5b).  Rebuilt queues repack into the identical layout.  Releases
    sticky bindings but never moves a live word — :func:`migrate` is
    the true defragmentation pass."""
    if kind != "chunk":
        return state
    lay, st = _views(cfg, kind, family, state.mem, state.ctl)
    st = chunk_alloc.compact(cfg, family, st)
    # not allocator traffic: telemetry words carry through unchanged
    return arena.pack(lay, st.q, st.ctx, st.meta,
                      tele=arena.tele_of(lay, state.ctl))


# ---- defragmentation: plan (shared jnp oracle) + migrate (execute) --------

def defrag_plan(cfg: HeapConfig, kind: str, family: str,
                state: arena.Arena, max_moves: int):
    """Relocation plan for one wave (core/defrag.py, DESIGN.md §10).
    Pure jnp, computed ONCE and shared verbatim by every backend —
    the forwarding-table analogue of ``shards.home_shards``."""
    return defrag.plan_math(cfg, kind, family, state.mem, state.ctl,
                            max_moves=max_moves)


def migrate(cfg: HeapConfig, kind: str, family: str, state: arena.Arena,
            src, dst, sizes, backend: str = "jnp",
            lowering: str = "auto") -> arena.Arena:
    """Execute one migration wave (copy extents, flip bitmap bits,
    retire emptied chunks, rebuild queues).  ``backend="pallas"`` runs
    the whole wave as ONE pallas_call (kernels/defrag_txn.py) under
    either lowering; ``"jnp"`` is the replay oracle — bit-identical,
    word for word (tests/test_defrag.py)."""
    _check_backend(backend)
    if kind != "chunk":
        return state
    if backend == "pallas":
        from repro.kernels import ops as kops
        mem, ctl = kops.arena_defrag_txn(cfg, kind, family, state.mem,
                                         state.ctl, src, dst, sizes,
                                         lowering=lowering)
    else:
        mem, ctl = defrag.migrate_math(cfg, kind, family, state.mem,
                                       state.ctl, src, dst, sizes)
    return arena.Arena(mem=mem, ctl=ctl)


# ---------------------------------------------------------------------------
# sharded transactions: serial replay oracle + the sharded dispatcher
# ---------------------------------------------------------------------------
#
# The sharded correctness contract (DESIGN.md §9) is a SCHEDULE: a bulk
# transaction over S shards behaves exactly as if the wavefront were
# replayed serially through S independent single-arena allocators,
# attempt-major then shard-minor —
#
#     for attempt a in 0..walk:
#         for shard s in 0..S-1:
#             serve the still-unserved lanes whose (home + a) % S == s
#
# ``sharded_alloc_math``/``sharded_free_math`` below ARE that replay
# (the jnp oracle); the Pallas lowerings grid the same schedule into
# ONE pallas_call (kernels/alloc_txn.sharded_arena_*_txn and
# kernels/alloc_txn_blocked.sharded_arena_*_txn_blocked), so
# bit-identity with the serial replay is checked word for word by
# tests/test_alloc_txn_parity.py.

def sharded_alloc_math(cfg: HeapConfig, num_shards: int, kind: str,
                       family: str, mem, ctl, sizes_bytes, mask, home,
                       walk: int) -> Tuple:
    """Serial single-shard oracle replay of one sharded alloc.  Lanes
    route to ``home`` first; lanes a shard cannot serve retry on the
    next ``walk`` neighbor shards.  Returns (mem', ctl', offsets) with
    offsets GLOBAL (shard · shard_words + local; −1 = every visited
    shard failed the lane).

    The replay is a nested ``lax.scan`` over (attempt, shard) rather
    than an unrolled loop: the schedule is identical step for step (so
    results are bit-identical to the gridded kernels), but the
    single-arena transaction math compiles ONCE instead of
    (walk+1)·num_shards times — for chunk variants that is the
    difference between seconds and minutes of XLA compile."""
    import jax

    scfg = shards.shard_config(cfg, num_shards)
    Ws = scfg.total_words
    n = sizes_bytes.shape[0]
    S = num_shards

    def shard_step(carry, s):
        mem, ctl, offs, a = carry
        sel = mask & ((home + a) % S == s) & (offs < 0)
        m2, c2, local = alloc_math(
            scfg, kind, family,
            jax.lax.dynamic_index_in_dim(mem, s, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(ctl, s, 0, keepdims=False),
            sizes_bytes, sel, attempt=a)
        mem = jax.lax.dynamic_update_index_in_dim(mem, m2, s, 0)
        ctl = jax.lax.dynamic_update_index_in_dim(ctl, c2, s, 0)
        offs = jnp.where(sel & (local >= 0), s * Ws + local, offs)
        return (mem, ctl, offs, a), None

    def attempt_step(carry, a):
        mem, ctl, offs = carry
        (mem, ctl, offs, _), _ = jax.lax.scan(
            shard_step, (mem, ctl, offs, a),
            jnp.arange(S, dtype=jnp.int32))
        return (mem, ctl, offs), None

    offs0 = jnp.full(n, -1, jnp.int32)
    (mem, ctl, offs), _ = jax.lax.scan(
        attempt_step, (mem, ctl, offs0),
        jnp.arange(walk + 1, dtype=jnp.int32))
    return mem, ctl, offs


def sharded_free_math(cfg: HeapConfig, num_shards: int, kind: str,
                      family: str, mem, ctl, offsets_words, sizes_bytes,
                      mask) -> Tuple:
    """Serial replay of one sharded free: each lane's owning shard is
    determined by its global offset (no overflow walk — an offset lives
    on exactly one shard), shards visited in order (a ``lax.scan``, as
    in :func:`sharded_alloc_math`)."""
    import jax

    scfg = shards.shard_config(cfg, num_shards)
    Ws = scfg.total_words
    sh = jnp.where(offsets_words >= 0, offsets_words // Ws, -1)

    def shard_step(carry, s):
        mem, ctl = carry
        sel = mask & (sh == s)
        local = jnp.where(sel, offsets_words - s * Ws, -1)
        m2, c2 = free_math(
            scfg, kind, family,
            jax.lax.dynamic_index_in_dim(mem, s, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(ctl, s, 0, keepdims=False),
            local, sizes_bytes, sel)
        mem = jax.lax.dynamic_update_index_in_dim(mem, m2, s, 0)
        ctl = jax.lax.dynamic_update_index_in_dim(ctl, c2, s, 0)
        return (mem, ctl), None

    (mem, ctl), _ = jax.lax.scan(shard_step, (mem, ctl),
                                 jnp.arange(num_shards, dtype=jnp.int32))
    return mem, ctl


def sharded_alloc(cfg: HeapConfig, num_shards: int, kind: str,
                  family: str, state: shards.ShardedArena, sizes_bytes,
                  mask, home, walk: int, backend: str = "jnp",
                  lowering: str = "auto"):
    """One bulk sharded allocation transaction (see module docstring
    for the schedule).  ``home`` is the per-lane home-shard vector
    (``shards.home_shards``), shared by every backend so routing can
    never diverge.  Still ONE pallas_call under ``backend="pallas"``:
    the kernels grid the (attempt, shard) schedule."""
    _check_backend(backend)
    if backend == "pallas":
        from repro.kernels import ops as kops
        mem, ctl, offs = kops.sharded_arena_alloc_txn(
            cfg, num_shards, kind, family, state.mem, state.ctl,
            sizes_bytes, mask, home, walk, lowering=lowering)
    else:
        mem, ctl, offs = sharded_alloc_math(
            cfg, num_shards, kind, family, state.mem, state.ctl,
            sizes_bytes, mask, home, walk)
    return shards.ShardedArena(mem=mem, ctl=ctl), offs


def sharded_free(cfg: HeapConfig, num_shards: int, kind: str,
                 family: str, state: shards.ShardedArena, offsets_words,
                 sizes_bytes, mask, backend: str = "jnp",
                 lowering: str = "auto"):
    _check_backend(backend)
    if backend == "pallas":
        from repro.kernels import ops as kops
        mem, ctl = kops.sharded_arena_free_txn(
            cfg, num_shards, kind, family, state.mem, state.ctl,
            offsets_words, sizes_bytes, mask, lowering=lowering)
    else:
        mem, ctl = sharded_free_math(
            cfg, num_shards, kind, family, state.mem, state.ctl,
            offsets_words, sizes_bytes, mask)
    return shards.ShardedArena(mem=mem, ctl=ctl)


def sharded_compact(cfg: HeapConfig, num_shards: int, kind: str,
                    family: str,
                    state: shards.ShardedArena) -> shards.ShardedArena:
    """Per-shard chunk rebind (shards are independent heaps)."""
    if kind != "chunk":
        return state
    scfg = shards.shard_config(cfg, num_shards)
    subs = [compact(scfg, kind, family, shards.take_shard(state, s))
            for s in range(num_shards)]
    return shards.ShardedArena(mem=jnp.stack([a.mem for a in subs]),
                               ctl=jnp.stack([a.ctl for a in subs]))


def sharded_defrag_plan(cfg: HeapConfig, num_shards: int, kind: str,
                        family: str, state: shards.ShardedArena,
                        max_moves: int):
    """Per-shard compaction plans merged to GLOBAL offsets (cross-shard
    rebalance plans come from ``shards.rebalance_plan_math``; both
    execute through :func:`sharded_migrate`)."""
    return defrag.sharded_plan_math(cfg, num_shards, kind, family,
                                    state.mem, state.ctl,
                                    max_moves=max_moves)


def sharded_migrate(cfg: HeapConfig, num_shards: int, kind: str,
                    family: str, state: shards.ShardedArena, src, dst,
                    sizes, backend: str = "jnp",
                    lowering: str = "auto") -> shards.ShardedArena:
    """Execute one sharded migration wave: extract every source shard's
    extents into a carry buffer, then insert + rebuild every shard —
    the (phase, shard) schedule ``defrag.sharded_migrate_math`` replays
    serially and both Pallas lowerings grid into ONE pallas_call.
    Cross-shard moves (rebalancing) ride the same wave."""
    _check_backend(backend)
    if kind != "chunk":
        return state
    if backend == "pallas":
        from repro.kernels import ops as kops
        mem, ctl = kops.sharded_arena_defrag_txn(
            cfg, num_shards, kind, family, state.mem, state.ctl, src,
            dst, sizes, lowering=lowering)
    else:
        mem, ctl = defrag.sharded_migrate_math(
            cfg, num_shards, kind, family, state.mem, state.ctl, src,
            dst, sizes)
    return shards.ShardedArena(mem=mem, ctl=ctl)
