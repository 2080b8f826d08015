"""Allocator cells: the paper's §3 iteration on one carried arena.

Each iteration is alloc → write a pattern → read it back → free, through
``Ouroboros.alloc`` / ``write_pattern`` / ``check_pattern`` / ``free``,
and syncs once: on the read-back result and the granted offsets, which
the host keeps.  After the window every iteration's offsets are checked
here, independently of the program: each granted region lies inside the
heap and no two overlap.  On a few iterations drawn from the seed, the
heap words as the write left them are kept, and here on the host every
word of every granted region must hold its lane's tag; the arena state
before and after is kept too, and the program's jnp engine replays
those iterations: offsets and every arena word must agree.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from bench import stats, xplane

WARM_S = 3.0


def _heap(cfg: dict):
    from repro.core import HeapConfig
    return HeapConfig(**cfg["heap"])


def overlaps(offs: np.ndarray, words: int) -> int:
    """Granted regions (``offs >= 0``, ``words`` long each) that overlap
    the next one in address order."""
    g = np.sort(offs[offs >= 0])
    return int(np.sum(g[1:] < g[:-1] + words))


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        counter, devs, control: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import Ouroboros

    cfg, mix = cell["config"], cell["mix"]
    heap = _heap(cfg)
    ouro = Ouroboros(heap, cfg["variant"], cfg["backend"], cfg["lowering"])
    lanes, size = mix["lanes"], mix["size_bytes"]
    rng = np.random.default_rng(seed)
    sizes = jnp.full(lanes, size, jnp.int32)
    mask = jnp.ones(lanes, bool)
    tags = jnp.asarray(rng.integers(1, 2 ** 31 - 1, lanes, dtype=np.int64)
                       .astype(np.int32))
    alloc = ouro.alloc
    if control:
        alloc = _overlapping(ouro.alloc, size)

    state = {"st": ouro.init(), "n": 0}
    offs_log: List[np.ndarray] = []
    ok_log: List[np.ndarray] = []
    times: List[tuple] = []
    keep = {}

    def iterate():
        t0 = time.perf_counter()
        i = state["n"]
        st = state["st"]
        if i in keep:
            keep[i]["pre"] = jax.tree.map(jnp.copy, st)
        with jax.profiler.TraceAnnotation("bench.iteration"):
            st, offs = alloc(st, sizes, mask)
            st = ouro.write_pattern(st, offs, sizes, tags)
            if i in keep:
                keep[i]["written"] = jnp.copy(ouro.heap(st))
            ok = ouro.check_pattern(st, offs, sizes, tags)
            offs_h, ok_h = jax.device_get((offs, ok))   # the one sync
            st = ouro.free(st, offs, sizes, mask)
        if i in keep:
            keep[i]["post"] = jax.tree.map(jnp.copy, st)
            keep[i]["offs"] = offs_h
        state["st"] = st
        state["n"] = i + 1
        offs_log.append(offs_h)
        ok_log.append(ok_h)
        times.append((t0, time.perf_counter()))

    # set-up: compile alloc, write, check and free, then run on for
    # WARM_S so that the window starts at its steady pace; two timed
    # iterations at least give that pace
    t_warm = time.perf_counter() + WARM_S
    while state["n"] < 3 or time.perf_counter() < t_warm:
        iterate()
    # the copies the window keeps of drawn iterations, warmed here
    jax.block_until_ready((jax.tree.map(jnp.copy, state["st"]),
                           jnp.copy(ouro.heap(state["st"]))))
    per = (times[-1][1] - times[1][0]) / (len(times) - 1)
    est = state["n"] + max(int(seconds / per), 2)
    pick = sorted(rng.choice(np.arange(state["n"], est),
                             size=min(mix["oracle_iterations"],
                                      est - state["n"]), replace=False))
    keep.update({int(i): {} for i in pick})
    n0 = state["n"]
    del offs_log[:], ok_log[:], times[:]

    def until(t_end):
        while time.perf_counter() < t_end:
            iterate()
        jax.block_until_ready(state["st"])

    t0 = time.perf_counter()
    counter.armed = True
    out = dict(system="allocator", lanes=lanes, mix=mix, sizes=cfg)
    if traced:
        out["trace"] = xplane.capture(lambda: until(t0 + mix["trace_s"]))
        out["trace_iterations"] = len(times)
    until(t0 + seconds)
    counter.armed = False
    t1 = time.perf_counter()
    offs = np.stack(offs_log)
    ok = np.stack(ok_log)
    granted = offs >= 0
    out.update(window=(t0, t1), setup_s=t0 - t_start,
               iterations=len(times), iter_times=times,
               granted=int(granted.sum()),
               segments=stats.segment_rates(
                   ((e, int(np.sum(o >= 0))) for (_, e), o in
                    zip(times, offs_log)), t0, t1),
               attempted=int(offs.size), failed=int((~granted).sum()))
    from bench import device
    out["device"] = device.describe(devs, out.get("trace"))

    words = -(-size // 4)
    heap_words = heap.total_words
    # the write fills the region's whole words with the lane's tag
    written = max(size // 4, 1)
    tags_h = np.asarray(tags)
    bad_words = 0
    for k in keep.values():
        if "written" in k:
            g = k["offs"] >= 0
            idx = k["offs"][g, None] + np.arange(written)[None, :]
            got = np.asarray(k["written"])[np.minimum(idx, heap_words - 1)]
            bad_words += int(np.sum((got != tags_h[g, None])
                                    | (idx >= heap_words)))
    oracle = Ouroboros(heap, cfg["variant"], "jnp")
    mism = 0
    for i, k in sorted(keep.items()):
        if "post" not in k:
            continue
        st, o = oracle.alloc(k["pre"], sizes, mask)
        mism += int(np.sum(np.asarray(o) != k["offs"]))
        st = oracle.write_pattern(st, o, sizes, tags)
        st = oracle.free(st, o, sizes, mask)
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(k["post"])):
            mism += int(np.sum(np.asarray(a) != np.asarray(b)))
    out["oracle_iterations"] = sum("post" in k for k in keep.values())
    out["checks"] = {
        "ungranted": (int((~granted).sum()), 0),
        "out_of_heap": (int(np.sum(granted & ((offs < 0)
                                              | (offs + words > heap_words)))),
                        0),
        "overlaps": (int(sum(overlaps(o, words) for o in offs)), 0),
        "readback_fail": (int(np.sum(granted & ~ok)), 0),
        "readback_words": (bad_words if out["oracle_iterations"]
                           else 2 ** 31, 0),
        # no iteration replayed: nothing proven, so the check fails
        "oracle_mismatch": (mism if out["oracle_iterations"] else 2 ** 31,
                            0),
    }
    out["first_iteration"] = n0
    return out


def _overlapping(alloc, size: int):
    """The control: the program's alloc with every odd lane handed the
    region of the lane before it, half a region on (two grants share
    words, the guarantee the configuration states)."""
    import jax.numpy as jnp

    def bad(st, sizes, mask):
        st, offs = alloc(st, sizes, mask)
        lane = jnp.arange(offs.shape[0])
        prev = jnp.roll(offs, 1) + (size // 8)
        return st, jnp.where(lane % 2 == 1, prev, offs)

    return bad
