"""Differential harness: the fused-arena Pallas backend vs the jnp
reference oracle, on randomized alloc/free/write/check traces.

For every variant the same trace is replayed through
``Ouroboros(cfg, variant, backend="jnp")`` and ``backend="pallas"``
under BOTH kernel lowerings — ``whole`` (full-arena refs) and
``blocked`` (the compiled lowering, DESIGN.md §8) — in interpret mode
on CPU, which pins the semantics but accepts kernels the chip's
compiler refuses (tests/test_tpu_compile.py covers that) — and all
three executions must be **bit-identical** at every step:

  - granted offsets and failure masks (−1 lanes)
  - ``check_pattern`` integrity verdicts
  - the full arena: every word of ``mem`` (heap, pool ring, queue ring
    or segment directory, chunk bitmaps) and of ``ctl`` (every counter)

Beyond lockstep equality this file pins the arena-era contracts:

  - one ``pallas_call`` per whole transaction (alloc and free), for all
    six variants and BOTH lowerings, asserted on the jaxpr — the
    fusion criterion survives the region-blocked refactor;
  - va/vl segment grow/shrink runs *inside* that one kernel: the
    small-chunk config below forces directory/chain growth and
    segment reclaim mid-trace (asserted via the pool counters, which
    only move on segment traffic for page-kind virtualized variants);
  - ``init`` state is backend-free, so a live heap can switch backends
    mid-stream and stay on the oracle's trajectory.

The sharded allocator (core/shards.py, DESIGN.md §9) extends the
matrix: with ``num_shards=4`` every variant is additionally held
bit-identical — offsets, failure lanes, every word of every shard —
to an explicit ``SerialShardOracle`` built from four independent
single-shard jnp allocators replayed in the documented
attempt-major/shard-minor schedule, and the one-kernel property is
asserted for the sharded grids of BOTH lowerings.

``--runslow`` unlocks the long replays (more ops, more seeds, both
configs × all six variants) that the scheduled CI job runs nightly.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import HeapConfig, Ouroboros, VARIANTS

CFG = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
                 min_page_bytes=16)
SIZES = [16, 24, 100, 256, 1000, 2048, 8192]  # 8192 > chunk → must fail

# Tiny chunks (16 words, so 15/16 queue slots per segment) make the
# virtualized queues cross a segment boundary every lane-width of
# traffic: init fills class 0 to exactly a segment edge, so the first
# class-0 free grows the directory/chain and a handful of allocs
# consume a whole segment and return it to the pool (shrink) — both
# paths of the in-kernel walk fire within a short trace.
GROW_CFG = HeapConfig(total_bytes=1 << 16, chunk_bytes=64,
                      min_page_bytes=16)
GROW_SIZES = [16, 32, 64, 128]                # 128 > chunk → must fail

N = 16       # fixed lane width so every transaction reuses one jit cache
OPS = 8
SEEDS = (0, 1)

# the Pallas implementations replayed in lockstep against the oracle
LOWERINGS = ("whole", "blocked")

VIRT_VARIANTS = tuple(v for v in VARIANTS if "_" in v)


def _assert_state_equal(variant, step, sj, sp):
    la, lb = jax.tree.leaves(sj), jax.tree.leaves(sp)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{variant}: state diverged after op {step}")


def _replay(variant, seed, cfg=CFG, sizes_menu=SIZES, ops=OPS):
    """Lockstep replay: the jnp oracle vs the Pallas backend under
    every kernel lowering, word-identical arenas after every op."""
    rng = np.random.default_rng(seed)
    oj = Ouroboros(cfg, variant, backend="jnp")
    ops_p = [Ouroboros(cfg, variant, backend="pallas", lowering=lw)
             for lw in LOWERINGS]
    sj = oj.init()
    sps = [o.init() for o in ops_p]
    for lw, sp in zip(LOWERINGS, sps):
        _assert_state_equal(f"{variant}/{lw}", "init", sj, sp)
    pool_sl = slice(oj.layout.off_pool_front, oj.layout.off_pool_back + 1)
    pool_ctr0 = np.asarray(sj.ctl)[pool_sl].copy()
    pool_moved = False

    live = []  # (offset, size) granted and not yet freed
    tagc = 0
    for step in range(ops):
        kind = rng.choice(["alloc", "free"]) if live else "alloc"
        if kind == "alloc":
            sizes = jnp.asarray(rng.choice(sizes_menu, N), jnp.int32)
            mask = jnp.asarray(rng.random(N) < 0.85)
            sj, offj = oj.alloc(sj, sizes, mask)
            offj = np.asarray(offj)
            outs = [o.alloc(s, sizes, mask)
                    for o, s in zip(ops_p, sps)]
            sps = [s for s, _ in outs]
            for lw, (_, offp) in zip(LOWERINGS, outs):
                np.testing.assert_array_equal(
                    offj, np.asarray(offp),
                    err_msg=f"{variant}/{lw}: offsets/failure masks "
                            f"diverged at op {step}")
            tags = jnp.arange(tagc, tagc + N, dtype=jnp.int32)
            tagc += N
            so = jnp.asarray(offj, jnp.int32)
            sj = oj.write_pattern(sj, so, sizes, tags)
            sps = [o.write_pattern(s, so, sizes, tags)
                   for o, s in zip(ops_p, sps)]
            cj = np.asarray(oj.check_pattern(sj, so, sizes, tags))
            for lw, o, s in zip(LOWERINGS, ops_p, sps):
                cp = np.asarray(o.check_pattern(s, so, sizes, tags))
                np.testing.assert_array_equal(
                    cj, cp, err_msg=f"{variant}/{lw}: integrity "
                                    f"verdicts diverged at op {step}")
            live.extend((int(o), int(s))
                        for o, s in zip(offj, np.asarray(sizes)) if o >= 0)
        else:
            k = min(len(live), int(rng.integers(1, N + 1)))
            pick = rng.choice(len(live), k, replace=False)
            drop = [live[i] for i in pick]
            live = [x for i, x in enumerate(live) if i not in set(pick)]
            fo = np.full(N, -1, np.int32)
            fs = np.zeros(N, np.int32)
            fo[:k] = [o for o, _ in drop]
            fs[:k] = [s for _, s in drop]
            fm = jnp.asarray(fo >= 0)
            sj = oj.free(sj, jnp.asarray(fo), jnp.asarray(fs), fm)
            sps = [o.free(s, jnp.asarray(fo), jnp.asarray(fs), fm)
                   for o, s in zip(ops_p, sps)]
        for lw, sp in zip(LOWERINGS, sps):
            _assert_state_equal(f"{variant}/{lw}", step, sj, sp)
        pool_moved |= bool(
            (np.asarray(sj.ctl)[pool_sl] != pool_ctr0).any())
    return pool_moved


@pytest.mark.compiled_lowering
@pytest.mark.parametrize("variant", VARIANTS)
def test_backends_bit_identical(variant):
    for seed in SEEDS:
        _replay(variant, seed)


@pytest.mark.compiled_lowering
@pytest.mark.parametrize("variant", VIRT_VARIANTS)
def test_backends_bit_identical_with_segment_churn(variant):
    """Small-chunk config: the va/vl segment walk grows and shrinks
    segments mid-trace, entirely inside the fused kernel."""
    pool_moved = _replay(variant, 3, cfg=GROW_CFG, sizes_menu=GROW_SIZES,
                         ops=10)
    if variant in ("va_page", "vl_page"):
        # For page-kind virtualized variants the pool only moves on
        # queue-segment grow/shrink — proof the trace exercised both
        # paths of the in-kernel walk.
        assert pool_moved, "trace never grew/shrank a queue segment"


@pytest.mark.slow
@pytest.mark.parametrize("variant", VARIANTS)
def test_backends_bit_identical_long_traces(variant):
    """Nightly CI sweep: longer traces, more seeds, both heap shapes."""
    for seed in (0, 1, 2):
        _replay(variant, seed, ops=24)
        _replay(variant, seed + 10, cfg=GROW_CFG, sizes_menu=GROW_SIZES,
                ops=24)


# ---- the fusion criterion: ONE kernel per whole transaction ---------------

from repro.kernels.ops import count_pallas_calls as _count_pallas_calls


@pytest.mark.compiled_lowering
@pytest.mark.parametrize("lowering", LOWERINGS)
@pytest.mark.parametrize("variant", VARIANTS)
def test_single_pallas_call_per_txn(variant, lowering):
    """backend="pallas": alloc and free each lower to exactly one
    pallas_call — the entire transaction (rank, grant, ring traffic,
    bitmap claim, va/vl segment walk) is device-fused — under BOTH the
    whole-arena and the region-blocked lowering.  The jnp oracle
    lowers to zero."""
    sizes = jnp.full(N, 64, jnp.int32)
    mask = jnp.ones(N, bool)
    offs = jnp.full(N, -1, jnp.int32)
    for backend, want in (("pallas", 1), ("jnp", 0)):
        o = Ouroboros(CFG, variant, backend, lowering)
        st = o.init()
        ja = jax.make_jaxpr(lambda s, z, m: o.alloc(s, z, m))(
            st, sizes, mask)
        jf = jax.make_jaxpr(lambda s, x, z, m: o.free(s, x, z, m))(
            st, offs, sizes, mask)
        assert _count_pallas_calls(ja) == want, (
            f"{variant}/{backend}: alloc is not a single fused kernel")
        assert _count_pallas_calls(jf) == want, (
            f"{variant}/{backend}: free is not a single fused kernel")


# ---- backend plumbing -----------------------------------------------------

def test_backend_validated():
    with pytest.raises(ValueError, match="backend"):
        Ouroboros(CFG, "page", backend="cuda")
    with pytest.raises(ValueError, match="backend"):
        # the dispatcher itself refuses typos too — nothing silently
        # falls through to the jnp branch
        from repro.core import transactions
        o = Ouroboros(CFG, "page")
        transactions.alloc(CFG, "page", "ring", o.init(),
                           jnp.full(4, 64, jnp.int32),
                           jnp.ones(4, bool), backend="palas")


def test_lowering_validated():
    with pytest.raises(ValueError, match="lowering"):
        Ouroboros(CFG, "page", backend="pallas", lowering="bocked")
    from repro.kernels.ops import resolve_lowering
    with pytest.raises(ValueError, match="lowering"):
        resolve_lowering("bocked")
    assert resolve_lowering("whole") == "whole"
    assert resolve_lowering("blocked") == "blocked"
    assert resolve_lowering("auto") in ("whole", "blocked")


def test_backends_share_init_state():
    """A heap can switch backends mid-stream: init is backend-free."""
    oj = Ouroboros(CFG, "page", backend="jnp")
    op = Ouroboros(CFG, "page", backend="pallas")
    st = oj.init()
    sizes = jnp.full(8, 64, jnp.int32)
    mask = jnp.ones(8, bool)
    st, offs = op.alloc(st, sizes, mask)   # pallas txn on jnp-built state
    st = oj.free(st, offs, sizes, mask)    # jnp txn on pallas-built state
    st2, offs2 = op.alloc(st, sizes, mask)
    assert (np.asarray(offs2) >= 0).all()


# ---- sharded allocator: the serial single-shard oracle replay -------------
#
# DESIGN.md §9's correctness contract: a sharded transaction behaves
# exactly as if the wavefront were replayed serially through S
# independent single-arena allocators — attempt-major, shard-minor,
# still-unserved lanes retrying on neighbor shards.  The class below IS
# that replay, built from S *single-shard* jnp Ouroboros instances (the
# oracle of everything above), and the sharded implementations — jnp,
# pallas/whole, pallas/blocked — must match it bit for bit: offsets,
# failure lanes, and every per-shard arena word.

SHARDS = 4
SHARD_SEEDS = (0,)
SHARD_OPS = 5


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _oracle_alloc_math(cfg, kind, family, mem, ctl, sizes, sel, attempt):
    from repro.core import transactions
    return transactions.alloc_math(cfg, kind, family, mem, ctl,
                                   sizes, sel, attempt=attempt)


class SerialShardOracle:
    """S independent single-shard jnp allocators replayed serially."""

    def __init__(self, cfg, variant, num_shards, walk):
        from repro.core import shards
        self.S, self.walk = num_shards, walk
        self.scfg = shards.shard_config(cfg, num_shards)
        self.Ws = self.scfg.total_words
        self.ouro = Ouroboros(self.scfg, variant)          # jnp oracle
        self.states = [self.ouro.init() for _ in range(num_shards)]

    def alloc(self, sizes, mask, home):
        n = int(sizes.shape[0])
        offs = np.full(n, -1, np.int64)
        mask, home = np.asarray(mask), np.asarray(home)
        for a in range(self.walk + 1):
            for s in range(self.S):
                sel = mask & ((home + a) % self.S == s) & (offs < 0)
                st = self.states[s]
                # alloc_math directly (not Ouroboros.alloc) so the
                # walk-depth telemetry histogram attributes served
                # lanes to attempt a, as the sharded impls do; jitted
                # (attempt traced) so the chunk-claim while_loop
                # compiles inside one program, as every production
                # caller of the math does
                mem2, ctl2, local = _oracle_alloc_math(
                    self.scfg, self.ouro.kind, self.ouro.family,
                    st.mem, st.ctl, sizes, jnp.asarray(sel),
                    jnp.asarray(a, jnp.int32))
                self.states[s] = st._replace(mem=mem2, ctl=ctl2)
                local = np.asarray(local)
                offs = np.where(sel & (local >= 0),
                                s * self.Ws + local, offs)
        return offs.astype(np.int32)

    def free(self, offsets, sizes, mask):
        offsets, mask = np.asarray(offsets), np.asarray(mask)
        owner = np.where(offsets >= 0, offsets // self.Ws, -1)
        for s in range(self.S):
            sel = mask & (owner == s)
            local = np.where(sel, offsets - s * self.Ws, -1)
            self.states[s] = self.ouro.free(
                self.states[s], jnp.asarray(local.astype(np.int32)),
                sizes, jnp.asarray(sel))

    def write(self, offsets, sizes, tags):
        """Per-shard write_pattern with shard-local offsets — the
        word-for-word equivalent of the sharded global-heap write."""
        offsets = np.asarray(offsets)
        owner = np.where(offsets >= 0, offsets // self.Ws, -1)
        for s in range(self.S):
            local = np.where(owner == s, offsets - s * self.Ws,
                             -1).astype(np.int32)
            self.states[s] = self.ouro.write_pattern(
                self.states[s], jnp.asarray(local), sizes, tags)

    def check(self, offsets, sizes, tags):
        offsets = np.asarray(offsets)
        owner = np.where(offsets >= 0, offsets // self.Ws, -1)
        ok = np.zeros(offsets.shape[0], bool)
        for s in range(self.S):
            local = np.where(owner == s, offsets - s * self.Ws,
                             -1).astype(np.int32)
            ok |= np.asarray(self.ouro.check_pattern(
                self.states[s], jnp.asarray(local), sizes, tags))
        return ok

    def stacked(self):
        """(mem, ctl) stacked like shards.ShardedArena."""
        return (np.stack([np.asarray(st.mem) for st in self.states]),
                np.stack([np.asarray(st.ctl) for st in self.states]))


def _assert_matches_serial(variant, tag, serial, states):
    smem, sctl = serial.stacked()
    for (lbl, st) in states:
        np.testing.assert_array_equal(
            smem, np.asarray(st.mem),
            err_msg=f"{variant}/{lbl}: mem diverged from the serial "
                    f"single-shard oracle replay at {tag}")
        np.testing.assert_array_equal(
            sctl, np.asarray(st.ctl),
            err_msg=f"{variant}/{lbl}: ctl diverged from the serial "
                    f"single-shard oracle replay at {tag}")


def _replay_sharded(variant, seed, ops=SHARD_OPS):
    """Lockstep replay with num_shards=4: sharded jnp vs both Pallas
    lowerings vs the serial single-shard oracle replay."""
    from repro.core import shards
    rng = np.random.default_rng(seed)
    impls = [("jnp", Ouroboros(CFG, variant, num_shards=SHARDS)),
             ("pallas/whole", Ouroboros(CFG, variant, backend="pallas",
                                        lowering="whole",
                                        num_shards=SHARDS)),
             ("pallas/blocked", Ouroboros(CFG, variant,
                                          backend="pallas",
                                          lowering="blocked",
                                          num_shards=SHARDS))]
    serial = SerialShardOracle(CFG, variant, SHARDS, impls[0][1].walk)
    states = [(lbl, o.init()) for lbl, o in impls]
    home = np.asarray(shards.home_shards(N, SHARDS))  # the hashed homes

    live = []
    tagc = 0
    for step in range(ops):
        kind = rng.choice(["alloc", "free"]) if live else "alloc"
        if kind == "alloc":
            sizes = jnp.asarray(rng.choice(SIZES, N), jnp.int32)
            mask = jnp.asarray(rng.random(N) < 0.85)
            want = serial.alloc(sizes, mask, home)
            new = []
            for (lbl, o), (_, st) in zip(impls, states):
                st, offs = o.alloc(st, sizes, mask)
                np.testing.assert_array_equal(
                    want, np.asarray(offs),
                    err_msg=f"{variant}/{lbl}: sharded offsets diverged "
                            f"from the serial replay at op {step}")
                new.append((lbl, st))
            states = new
            # write/check through the GLOBAL heap view: the sharded
            # write_pattern must land the same words as the per-shard
            # writes of the serial oracle
            tags = jnp.arange(tagc, tagc + N, dtype=jnp.int32)
            tagc += N
            so = jnp.asarray(want, jnp.int32)
            serial.write(want, sizes, tags)
            states = [(lbl, o.write_pattern(st, so, sizes, tags))
                      for (lbl, o), (_, st) in zip(impls, states)]
            cj = serial.check(want, sizes, tags)
            for (lbl, o), (_, st) in zip(impls, states):
                cp = np.asarray(o.check_pattern(st, so, sizes, tags))
                np.testing.assert_array_equal(
                    cj, cp, err_msg=f"{variant}/{lbl}: integrity "
                                    f"verdicts diverged at op {step}")
            live.extend((int(o), int(s))
                        for o, s in zip(want, np.asarray(sizes))
                        if o >= 0)
        else:
            k = min(len(live), int(rng.integers(1, N + 1)))
            pick = rng.choice(len(live), k, replace=False)
            drop = [live[i] for i in pick]
            live = [x for i, x in enumerate(live) if i not in set(pick)]
            fo = np.full(N, -1, np.int32)
            fs = np.zeros(N, np.int32)
            fo[:k] = [o for o, _ in drop]
            fs[:k] = [s for _, s in drop]
            fm = jnp.asarray(fo >= 0)
            serial.free(fo, jnp.asarray(fs), fm)
            states = [(lbl, o.free(st, jnp.asarray(fo), jnp.asarray(fs),
                                   fm))
                      for (lbl, o), (_, st) in zip(impls, states)]
        _assert_matches_serial(variant, f"op {step}", serial, states)
    # homes must actually spread over the shards, or the walk schedule
    # was never multi-shard to begin with
    assert len(set(home.tolist())) > 1


@pytest.mark.compiled_lowering
@pytest.mark.parametrize("variant", VARIANTS)
def test_sharded_bit_identical_to_serial_oracle(variant):
    """num_shards=4: sharded jnp, whole, and blocked all replay the
    serial single-shard oracle schedule bit for bit (offsets, failure
    lanes, every word of every shard)."""
    for seed in SHARD_SEEDS:
        _replay_sharded(variant, seed)


@pytest.mark.compiled_lowering
@pytest.mark.parametrize("variant", ("page", "va_page", "vl_chunk"))
def test_sharded_pinned_fast_path_matches_serial(variant):
    """Static shard_hint + overflow_walk=0: the pinned fast path (only
    the hinted shard enters the kernel) stays on the serial-replay
    trajectory with a constant home and no walk."""
    from repro.core import shards
    hint = 2
    # 16 chunks per shard: enough for vl_chunk's init-time queue
    # segments (one per class) to leave data chunks in the pool
    pin_cfg = HeapConfig(total_bytes=1 << 17, chunk_bytes=1 << 11,
                         min_page_bytes=16)
    impls = [("jnp", Ouroboros(pin_cfg, variant, num_shards=SHARDS,
                               overflow_walk=0)),
             ("pallas/whole", Ouroboros(pin_cfg, variant,
                                        backend="pallas",
                                        lowering="whole",
                                        num_shards=SHARDS,
                                        overflow_walk=0)),
             ("pallas/blocked", Ouroboros(pin_cfg, variant,
                                          backend="pallas",
                                          lowering="blocked",
                                          num_shards=SHARDS,
                                          overflow_walk=0))]
    serial = SerialShardOracle(pin_cfg, variant, SHARDS, walk=0)
    states = [(lbl, o.init()) for lbl, o in impls]
    home = np.full(N, hint, np.int64)
    sizes = jnp.asarray([64, 256, 64, 1000] * (N // 4), jnp.int32)
    mask = jnp.ones(N, bool)

    want = serial.alloc(sizes, mask, home)
    granted = want >= 0
    # partial grants are fine (per-shard inventories are small) — the
    # contract under test is serial-replay equality + shard residency
    assert granted.any()
    Ws = shards.shard_config(pin_cfg, SHARDS).total_words
    assert set((want[granted] // Ws).tolist()) == {hint}, \
        "pinned grants must come from the hinted shard"
    new = []
    for (lbl, o), (_, st) in zip(impls, states):
        st, offs = o.alloc(st, sizes, mask, shard_hint=hint)
        np.testing.assert_array_equal(want, np.asarray(offs),
                                      err_msg=f"{variant}/{lbl}")
        new.append((lbl, st))
    states = new
    _assert_matches_serial(variant, "pinned-alloc", serial, states)

    serial.free(want, sizes, mask)
    states = [(lbl, o.free(st, jnp.asarray(want), sizes, mask,
                           shard_hint=hint))
              for (lbl, o), (_, st) in zip(impls, states)]
    _assert_matches_serial(variant, "pinned-free", serial, states)


@pytest.mark.compiled_lowering
@pytest.mark.parametrize("lowering", LOWERINGS)
@pytest.mark.parametrize("variant", ("page", "chunk", "va_page",
                                     "vl_chunk"))
def test_sharded_single_pallas_call_per_txn(variant, lowering):
    """The one-kernel property survives sharding: with num_shards=4 the
    (attempt, shard) schedule rides the grid of ONE pallas_call for
    alloc and free, under BOTH lowerings (jnp still lowers to zero)."""
    sizes = jnp.full(N, 64, jnp.int32)
    mask = jnp.ones(N, bool)
    offs = jnp.full(N, -1, jnp.int32)
    for backend, want in (("pallas", 1), ("jnp", 0)):
        o = Ouroboros(CFG, variant, backend, lowering,
                      num_shards=SHARDS)
        st = o.init()
        ja = jax.make_jaxpr(lambda s, z, m: o.alloc(s, z, m))(
            st, sizes, mask)
        jf = jax.make_jaxpr(lambda s, x, z, m: o.free(s, x, z, m))(
            st, offs, sizes, mask)
        assert _count_pallas_calls(ja) == want, (
            f"{variant}/{backend}/shards: alloc is not a single fused "
            f"kernel")
        assert _count_pallas_calls(jf) == want, (
            f"{variant}/{backend}/shards: free is not a single fused "
            f"kernel")


def test_shard_knobs_validated():
    from repro.core import shards
    with pytest.raises(ValueError, match="num_chunks"):
        # 32 chunks don't divide by 5
        Ouroboros(CFG, "page", num_shards=5)
    with pytest.raises(ValueError, match="overflow_walk"):
        Ouroboros(CFG, "page", num_shards=4, overflow_walk=-1)
    with pytest.raises(ValueError, match="overflow_walk"):
        # an ignored knob must not be silently accepted
        Ouroboros(CFG, "page", overflow_walk=2)
    with pytest.raises(ValueError, match="shard_hint"):
        o = Ouroboros(CFG, "page")
        o.alloc(o.init(), jnp.full(4, 64, jnp.int32),
                jnp.ones(4, bool), shard_hint=0)
    with pytest.raises(ValueError, match="shard_hint"):
        shards.home_shards(8, 4, jnp.zeros(5, jnp.int32))
    # walk resolution: None = all neighbors, ints clamp to S-1
    assert shards.resolve_walk(4, None) == 3
    assert shards.resolve_walk(4, 99) == 3
    assert shards.resolve_walk(4, 1) == 1


def test_numpy_integer_shard_hint_pins_like_python_int():
    """np.int32/np.int64 hints (e.g. an element of a hints array) must
    behave exactly like a Python int — including taking the pinned
    fast path when the walk is off."""
    from repro.core import shards
    o = Ouroboros(CFG, "page", num_shards=SHARDS, overflow_walk=0)
    sizes = jnp.full(4, 64, jnp.int32)
    mask = jnp.ones(4, bool)
    st_py, offs_py = o.alloc(o.init(), sizes, mask, shard_hint=2)
    st_np, offs_np = o.alloc(o.init(), sizes, mask,
                             shard_hint=np.int32(2))
    np.testing.assert_array_equal(np.asarray(offs_py),
                                  np.asarray(offs_np))
    _assert_state_equal("page/np-hint", "pinned", st_py, st_np)
    assert shards.static_hint(np.int64(3)) == 3
    assert shards.static_hint(3) == 3
    assert shards.static_hint(None) is None
    assert shards.static_hint(jnp.zeros(4, jnp.int32)) is None


@pytest.mark.compiled_lowering
@pytest.mark.parametrize("variant", ("page", "va_page", "vl_chunk"))
def test_midstream_backend_switch_stays_on_oracle_trajectory(variant):
    """Replaying a trace while hopping jnp→whole→blocked after every
    op lands bit-identically on the pure-jnp trajectory (the
    ouroboros.py promise that shared init state lets a heap switch
    backends — now including the kernel lowering)."""
    oj = Ouroboros(CFG, variant, backend="jnp")
    ow = Ouroboros(CFG, variant, backend="pallas", lowering="whole")
    ob = Ouroboros(CFG, variant, backend="pallas", lowering="blocked")
    rng = np.random.default_rng(7)
    ref, mix = oj.init(), oj.init()  # distinct buffers: alloc donates
    hop = [oj, ow, ob, ow, ob]  # jnp→whole→blocked→whole→blocked…
    tagc = 0
    live = []
    for step in range(len(hop) + 1):
        o = hop[step % len(hop)]
        if live and rng.random() < 0.4:
            k = min(len(live), N)
            fo = np.full(N, -1, np.int32)
            fs = np.zeros(N, np.int32)
            fo[:k] = [x[0] for x in live[:k]]
            fs[:k] = [x[1] for x in live[:k]]
            live = live[k:]
            fm = jnp.asarray(fo >= 0)
            ref = oj.free(ref, jnp.asarray(fo), jnp.asarray(fs), fm)
            mix = o.free(mix, jnp.asarray(fo), jnp.asarray(fs), fm)
        else:
            sizes = jnp.asarray(rng.choice(SIZES, N), jnp.int32)
            mask = jnp.asarray(rng.random(N) < 0.85)
            ref, offr = oj.alloc(ref, sizes, mask)
            mix, offm = o.alloc(mix, sizes, mask)
            np.testing.assert_array_equal(np.asarray(offr),
                                          np.asarray(offm))
            tags = jnp.arange(tagc, tagc + N, dtype=jnp.int32)
            tagc += N
            so = jnp.asarray(np.asarray(offr), jnp.int32)
            ref = oj.write_pattern(ref, so, sizes, tags)
            mix = o.write_pattern(mix, so, sizes, tags)
            live.extend((int(x), int(s)) for x, s in
                        zip(np.asarray(offr), np.asarray(sizes)) if x >= 0)
        _assert_state_equal(variant, f"switch-{step}", ref, mix)
