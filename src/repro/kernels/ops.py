"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend the kernels compile natively; on any other backend
they run in ``interpret=True`` mode.  Interpret mode pins the
word-level semantics but accepts kernels that Mosaic refuses, so
tests/test_tpu_compile.py compiles the blocked allocator kernels for a
described v5e chip.  ``ref.py`` carries the pure-jnp oracles used by
the tests.
"""
from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import alloc_txn as _alloc_txn
from repro.kernels.bitmap_select import bitmap_select as _bitmap_select
from repro.kernels.paged_attention import paged_attention as _paged_attention
from repro.kernels.ring_window import ring_window as _ring_window
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan


@functools.lru_cache(maxsize=None)
def _interpret() -> bool:
    """True off a TPU: every kernel then runs in the Pallas interpreter,
    which is said once per process so that a run whose chip was not
    found cannot pass for a compiled one."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    warnings.warn(f"Pallas kernels run in interpret mode on the "
                  f"{backend!r} backend, not compiled for a TPU",
                  RuntimeWarning, stacklevel=2)
    return True


def ring_window(store, front, counts, *, m: int):
    return _ring_window(store, front, counts, m=m, interpret=_interpret())


# ---- fused allocator transactions (kernels/alloc_txn.py) -------------------

def ring_txn_pop(store, front, back, cls, valid, *, limit: bool):
    return _alloc_txn.ring_txn_pop(store, front, back, cls, valid,
                                   limit=limit, interpret=_interpret())


def ring_txn_push(store, back, cls, vals, valid):
    return _alloc_txn.ring_txn_push(store, back, cls, vals, valid,
                                    interpret=_interpret())


def chunk_txn_claim(row, take, *, ppc: int):
    return _alloc_txn.chunk_txn_claim(row, take, ppc=ppc,
                                      interpret=_interpret())


LOWERINGS = ("whole", "blocked", "auto")


def resolve_lowering(lowering: str = "auto") -> str:
    """Concrete kernel lowering for the fused arena transactions.

    ``whole``    the kernel takes the full ``mem`` image as one ref and
                 runs the jnp math on it — interpret mode only: Mosaic
                 cannot lower it (no ``cumsum``);
    ``blocked``  the compiled lowering (kernels/alloc_txn_blocked.py):
                 scalar-core code over SMEM windows of the HBM regions
                 (DESIGN.md §8);
    ``auto``     honours ``REPRO_ALLOC_LOWERING`` (CI forces the
                 blocked matrix through it), else picks ``blocked`` on
                 TPU and ``whole`` in CPU interpret mode.
    """
    if lowering not in LOWERINGS:
        raise ValueError(
            f"unknown lowering {lowering!r}; pick from {LOWERINGS}")
    if lowering != "auto":
        return lowering
    env = os.environ.get("REPRO_ALLOC_LOWERING", "")
    if env:
        if env not in ("whole", "blocked"):
            raise ValueError(
                f"REPRO_ALLOC_LOWERING={env!r}; expected whole|blocked")
        return env
    return "blocked" if jax.default_backend() == "tpu" else "whole"


def arena_alloc_txn(cfg, kind, family, mem, ctl, sizes_bytes, mask,
                    lowering: str = "auto"):
    """Whole alloc transaction (any variant) in one pallas_call."""
    if resolve_lowering(lowering) == "blocked":
        from repro.kernels import alloc_txn_blocked as _blk
        return _blk.arena_alloc_txn_blocked(cfg, kind, family, mem, ctl,
                                            sizes_bytes, mask,
                                            interpret=_interpret())
    return _alloc_txn.arena_alloc_txn(cfg, kind, family, mem, ctl,
                                      sizes_bytes, mask,
                                      interpret=_interpret())


def arena_free_txn(cfg, kind, family, mem, ctl, offsets_words,
                   sizes_bytes, mask, lowering: str = "auto"):
    """Whole free transaction (any variant) in one pallas_call."""
    if resolve_lowering(lowering) == "blocked":
        from repro.kernels import alloc_txn_blocked as _blk
        return _blk.arena_free_txn_blocked(cfg, kind, family, mem, ctl,
                                           offsets_words, sizes_bytes,
                                           mask, interpret=_interpret())
    return _alloc_txn.arena_free_txn(cfg, kind, family, mem, ctl,
                                     offsets_words, sizes_bytes, mask,
                                     interpret=_interpret())


def sharded_arena_alloc_txn(cfg, num_shards, kind, family, mem, ctl,
                            sizes_bytes, mask, home, walk,
                            lowering: str = "auto"):
    """Whole SHARDED alloc transaction (overflow-walk schedule gridded
    over per-shard slabs, core/shards.py) in one pallas_call."""
    if resolve_lowering(lowering) == "blocked":
        from repro.kernels import alloc_txn_blocked as _blk
        return _blk.sharded_arena_alloc_txn_blocked(
            cfg, num_shards, kind, family, mem, ctl, sizes_bytes, mask,
            home, walk, interpret=_interpret())
    return _alloc_txn.sharded_arena_alloc_txn(
        cfg, num_shards, kind, family, mem, ctl, sizes_bytes, mask,
        home, walk, interpret=_interpret())


def sharded_arena_free_txn(cfg, num_shards, kind, family, mem, ctl,
                           offsets_words, sizes_bytes, mask,
                           lowering: str = "auto"):
    """Whole SHARDED free transaction in one pallas_call."""
    if resolve_lowering(lowering) == "blocked":
        from repro.kernels import alloc_txn_blocked as _blk
        return _blk.sharded_arena_free_txn_blocked(
            cfg, num_shards, kind, family, mem, ctl, offsets_words,
            sizes_bytes, mask, interpret=_interpret())
    return _alloc_txn.sharded_arena_free_txn(
        cfg, num_shards, kind, family, mem, ctl, offsets_words,
        sizes_bytes, mask, interpret=_interpret())


# ---- fused defragmentation waves (kernels/defrag_txn.py) -------------------

def arena_defrag_txn(cfg, kind, family, mem, ctl, src, dst, sizes,
                     lowering: str = "auto"):
    """One whole migration wave (DESIGN.md §10) in one pallas_call."""
    from repro.kernels import defrag_txn as _dfg
    if resolve_lowering(lowering) == "blocked":
        return _dfg.arena_defrag_txn_blocked(cfg, kind, family, mem, ctl,
                                             src, dst, sizes,
                                             interpret=_interpret())
    return _dfg.arena_defrag_txn(cfg, kind, family, mem, ctl, src, dst,
                                 sizes, interpret=_interpret())


def sharded_arena_defrag_txn(cfg, num_shards, kind, family, mem, ctl,
                             src, dst, sizes, lowering: str = "auto"):
    """One SHARDED migration wave (extract/insert phases gridded over
    the shards) in one pallas_call."""
    from repro.kernels import defrag_txn as _dfg
    if resolve_lowering(lowering) == "blocked":
        return _dfg.sharded_arena_defrag_txn_blocked(
            cfg, num_shards, kind, family, mem, ctl, src, dst, sizes,
            interpret=_interpret())
    return _dfg.sharded_arena_defrag_txn(
        cfg, num_shards, kind, family, mem, ctl, src, dst, sizes,
        interpret=_interpret())


def count_pallas_calls(closed_jaxpr) -> int:
    """Number of ``pallas_call`` eqns anywhere in a jaxpr (descending
    into sub-jaxprs in eqn params).  The single source of truth for the
    one-kernel-per-transaction assertions in tests/test_alloc_txn_parity
    and the ``launches_per_txn`` proof in benchmarks/run.py."""
    from jax.extend import core as jc

    def jaxprs_in(val):
        if isinstance(val, jc.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, jc.Jaxpr):
            yield val
        elif isinstance(val, (tuple, list)):
            for v in val:
                yield from jaxprs_in(v)
        elif isinstance(val, dict):
            for v in val.values():
                yield from jaxprs_in(v)

    seen = 0
    stack = [closed_jaxpr.jaxpr]
    while stack:
        jx = stack.pop()
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                seen += 1
            for val in eqn.params.values():
                stack.extend(jaxprs_in(val))
    return seen


def bitmap_select(words, k, *, block_words: int = 32):
    return _bitmap_select(words, k, block_words=block_words,
                          interpret=_interpret())


def bitmap_select_indices(words, k, *, max_k: int):
    """Compact the dense rank map to the first ``max_k`` bit indices."""
    dense = bitmap_select(words, k)
    order = jnp.argsort(jnp.where(dense >= 0, dense, jnp.int32(2**30)))
    idx = order[:max_k]
    valid = dense[idx] >= 0
    return jnp.where(valid, idx, -1).astype(jnp.int32), valid


def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    wpp=None):
    """Paged decode attention; ``wpp`` set means ``page_table`` holds
    raw arena word offsets (page id derived at DMA-issue time — see
    kernels/paged_attention.py)."""
    return _paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                            wpp=wpp,
                            interpret=_interpret())


def ssd_scan(x, dt, a, b, c, h0=None, *, chunk: int = 64):
    return _ssd_scan(x, dt, a, b, c, h0, chunk=chunk,
                     interpret=_interpret())
