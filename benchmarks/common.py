"""Shared benchmark machinery mirroring the paper's §3 methodology.

"the program iterates ten times through allocating memory, writing some
data, checking that the data is correct when read back and then freeing
the memory.  The average time for performing the allocations and frees
is calculated ... the code was modified to report the average over all
iterations, and the average over all but the first iteration"

The JIT parallel holds exactly: XLA compiles on the first call the way
SYCL JIT-compiles SPIR-V, so ``avg_all`` vs ``avg_subsequent`` is the
same apples-to-apples split the paper added.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import HeapConfig, Ouroboros

ITERS = 10
BENCH_HEAP = HeapConfig(total_bytes=32 << 20, chunk_bytes=8 << 10,
                        min_page_bytes=16)


def bench_variant(variant: str, *, n_allocs: int, size_bytes: int,
                  iters: int = ITERS, cfg: HeapConfig = BENCH_HEAP,
                  backend: str = "jnp", lowering: str = "auto",
                  num_shards: int = 1):
    """One paper-style measurement cell.  Returns dict with avg_all /
    avg_subsequent alloc+free µs and the data-integrity flag.

    ``backend`` selects the transaction implementation (jnp reference
    vs fused Pallas kernels) so every figure cell can report the two
    side by side — on CPU the Pallas path runs in interpret mode, so
    its timings are only meaningful on a TPU backend.  ``lowering``
    picks the Pallas kernel shape (whole-arena refs vs region-blocked;
    kernels/ops.resolve_lowering).  ``num_shards`` runs the cell on the
    sharded multi-arena allocator (core/shards.py): hashed home-shard
    routing, full overflow walk — the scaling axis of the shard
    sweep."""
    ouro = Ouroboros(cfg, variant, backend, lowering,
                     num_shards=num_shards)
    state = ouro.init()
    jax.block_until_ready(state)
    sizes = jnp.full(n_allocs, size_bytes, jnp.int32)
    mask = jnp.ones(n_allocs, bool)
    tags = jnp.arange(n_allocs, dtype=jnp.int32)

    alloc_t, free_t = [], []
    all_ok = True
    for it in range(iters):
        t0 = time.perf_counter()
        state, offs = ouro.alloc(state, sizes, mask)
        jax.block_until_ready(offs)
        alloc_t.append(time.perf_counter() - t0)

        state = ouro.write_pattern(state, offs, sizes, tags)
        ok = np.asarray(ouro.check_pattern(state, offs, sizes, tags))
        granted = np.asarray(offs) >= 0
        all_ok &= bool(ok[granted].all()) and bool(granted.any())

        t0 = time.perf_counter()
        state = ouro.free(state, offs, sizes, mask)
        jax.block_until_ready(state)
        free_t.append(time.perf_counter() - t0)

    from repro.kernels.ops import resolve_lowering
    us = lambda ts: 1e6 * float(np.mean(ts))
    return {
        "variant": variant, "backend": backend,
        "lowering": (resolve_lowering(lowering) if backend == "pallas"
                     else "none"),
        "num_shards": num_shards,
        "n": n_allocs, "size": size_bytes,
        "alloc_us_all": us(alloc_t),
        "alloc_us_subsequent": us(alloc_t[1:]),
        "free_us_all": us(free_t),
        "free_us_subsequent": us(free_t[1:]),
        "per_alloc_ns": 1e9 * float(np.mean(alloc_t[1:])) / n_allocs,
        "data_ok": all_ok,
    }


SIZE_SWEEP = (16, 64, 256, 1024, 4096, 8192)       # paper fig x-axis 1
THREAD_SWEEP = (32, 128, 512, 1024, 4096, 8192)    # paper fig x-axis 2
THREAD_SWEEP_CHUNK = (32, 128, 512, 1024, 2048)    # chunk walk is O(N/ppc)


def figure_rows(variant: str, quick: bool = False,
                backend: str = "jnp", lowering: str = "auto",
                num_shards: int = 1):
    """The two sweeps of one paper figure (size @1024 allocs; threads
    @1000 B), as the paper's figs. 1-6 do per allocator."""
    sizes = SIZE_SWEEP[::3] if quick else SIZE_SWEEP
    is_chunk = "chunk" in variant
    threads = (THREAD_SWEEP_CHUNK if is_chunk else THREAD_SWEEP)
    threads = threads[::3] if quick else threads
    rows = []
    for s in sizes:
        rows.append(bench_variant(variant, n_allocs=1024 if not quick
                                  else 256, size_bytes=s,
                                  backend=backend, lowering=lowering,
                                  num_shards=num_shards))
    for n in threads:
        rows.append(bench_variant(variant, n_allocs=n, size_bytes=1000,
                                  backend=backend, lowering=lowering,
                                  num_shards=num_shards))
    return rows


def pallas_calls_per_txn(variant: str, backend: str = "pallas",
                         lowering: str = "auto", num_shards: int = 1):
    """(alloc, free) pallas_call launch counts for one bulk transaction,
    read off the jaxpr — the proof of single-kernel fusion the arena
    refactor claims (1/1 for "pallas" under BOTH lowerings AND any
    ``num_shards`` — the sharded schedule rides the grid — 0/0 for
    "jnp").  Uses a small heap: the count is layout-independent and
    tracing stays cheap."""
    from repro.kernels.ops import count_pallas_calls as count

    cfg = HeapConfig(total_bytes=1 << 16, chunk_bytes=1 << 11,
                     min_page_bytes=16)
    ouro = Ouroboros(cfg, variant, backend, lowering,
                     num_shards=num_shards)
    st = ouro.init()
    sizes = jnp.full(16, 64, jnp.int32)
    mask = jnp.ones(16, bool)
    offs = jnp.full(16, -1, jnp.int32)
    ja = jax.make_jaxpr(lambda s, z, m: ouro.alloc(s, z, m))(
        st, sizes, mask)
    jf = jax.make_jaxpr(lambda s, o, z, m: ouro.free(s, o, z, m))(
        st, offs, sizes, mask)
    return count(ja), count(jf)


def pallas_calls_per_defrag_wave(variant: str, backend: str = "pallas",
                                 lowering: str = "auto",
                                 num_shards: int = 1):
    """pallas_call launch count for one whole defragmentation wave —
    plan AND migrate (DESIGN.md §10) — read off the jaxpr (1 for
    "pallas" under both lowerings and any ``num_shards``, 0 for
    "jnp")."""
    from repro.kernels.ops import count_pallas_calls as count

    cfg = HeapConfig(total_bytes=num_shards << 16, chunk_bytes=1 << 11,
                     min_page_bytes=16)
    ouro = Ouroboros(cfg, variant, backend, lowering,
                     num_shards=num_shards)
    st = ouro.init()
    return count(jax.make_jaxpr(
        lambda s: ouro.defrag(s, max_moves=32))(st))


def launches_per_tick(engine) -> int:
    """pallas_call launch count of ONE decode tick, read off the
    engine's fused tick program.  A thin
    delegate to ``ServingEngine.launches_per_tick`` — the SAME counter
    feeds ``engine.stats["launches_per_tick"]`` and the fig8 serving
    records, so the two can never disagree.  Constant in ``max_batch``
    (each tick is a fixed set of jitted programs; the grow transaction
    is a single kernel): 1 with ``alloc_backend="pallas"``, 0 with the
    jnp oracle."""
    return engine.launches_per_tick()


def alloc_comparison_cell(variant: str, *, quick: bool = False,
                          lowering: str = "auto"):
    """One jnp-vs-pallas cell per variant for BENCH_alloc.json — the
    perf-trajectory artifact future PRs diff against."""
    n = 128 if quick else 512
    cfg = HeapConfig(total_bytes=4 << 20, chunk_bytes=8 << 10,
                     min_page_bytes=16)
    out = {}
    for backend in ("jnp", "pallas"):
        r = bench_variant(variant, n_allocs=n, size_bytes=256,
                          iters=4 if quick else ITERS, cfg=cfg,
                          backend=backend, lowering=lowering)
        out[backend] = {
            "lowering": r["lowering"],
            "alloc_us_all": r["alloc_us_all"],
            "alloc_us_subsequent": r["alloc_us_subsequent"],
            "free_us_all": r["free_us_all"],
            "free_us_subsequent": r["free_us_subsequent"],
            "data_ok": r["data_ok"],
        }
    return out


# ---------------------------------------------------------------------------
# BENCH_serve.json trajectory schema (DESIGN.md §13)
#
# The file is append-only: {"runs": [record, ...]}, one record per
# benchmark invocation.  Two record kinds share the envelope —
# ``serve`` (fig8: tokens-per-second cells) and ``replay``
# (fig9: per-scenario traffic-replay telemetry cells).  Records written
# before the ``record`` key existed are ``serve`` records; the
# validator grandfathers them in rather than rewriting history.
# ---------------------------------------------------------------------------

SERVE_RECORD_KINDS = ("serve", "replay")
SERVE_RECORD_KEYS = ("platform", "git_sha", "record", "cells")
REPLAY_CELL_KEYS = (
    "scenario", "arch", "mode", "requests", "completed", "cancelled",
    "steps", "tokens", "tick_ms_p50", "tick_ms_p99", "queue_wait_p50",
    "queue_wait_p99", "evictions", "defrag_waves", "auto_defrag_waves",
    "pages_migrated", "aux_pages_per_slot", "allocs", "frees",
    "frag_ratio_final",
) + (
    # compile-pollution split (DESIGN.md §14): ticks that paid a jit
    # first-call are summed into compile_ms and EXCLUDED from the
    # steady percentiles; the unsplit p50/p99 above keep their
    # all-ticks meaning so old records stay comparable.  Cells written
    # before the split exist in the append-only trajectory; the
    # validator grandfathers a cell carrying NONE of these three,
    # like pre-``record`` envelopes, but a cell with any must have all.
    "compile_ms", "tick_ms_p50_steady", "tick_ms_p99_steady",
)

REPLAY_STEADY_KEYS = REPLAY_CELL_KEYS[-3:]


def validate_serve_record(record) -> str:
    """Schema-check one BENCH_serve.json run record; returns its kind.

    Required envelope keys: ``platform``, ``git_sha``, a non-empty
    ``cells`` dict, and a ``record`` kind from
    :data:`SERVE_RECORD_KINDS` — absent kind means a legacy fig8
    record and validates as ``"serve"``.  ``replay`` cells must carry
    every telemetry key in :data:`REPLAY_CELL_KEYS` (the p50/p99 +
    fragmentation trajectory future PRs diff against); cells written
    before the :data:`REPLAY_STEADY_KEYS` compile split are
    grandfathered without them.  Raises ``ValueError`` with the
    offending key on any violation."""
    if not isinstance(record, dict):
        raise ValueError(f"serve record must be a dict, got "
                         f"{type(record).__name__}")
    kind = record.get("record", "serve")
    if kind not in SERVE_RECORD_KINDS:
        raise ValueError(f"unknown serve record kind {kind!r}; expected "
                         f"one of {SERVE_RECORD_KINDS}")
    for key in SERVE_RECORD_KEYS:
        if key == "record":
            continue                      # legacy records predate it
        if key not in record:
            raise ValueError(f"serve record missing required key "
                             f"{key!r} (kind={kind})")
    cells = record["cells"]
    if not isinstance(cells, dict) or not cells:
        raise ValueError(f"serve record 'cells' must be a non-empty "
                         f"dict, got {cells!r}")
    if kind == "replay":
        for name, cell in cells.items():
            required = REPLAY_CELL_KEYS
            if not any(k in cell for k in REPLAY_STEADY_KEYS):
                # a cell predating the §14 compile split: grandfather
                # it in rather than rewriting the append-only history
                required = [k for k in required
                            if k not in REPLAY_STEADY_KEYS]
            missing = [k for k in required if k not in cell]
            if missing:
                raise ValueError(f"replay cell {name!r} missing "
                                 f"telemetry keys {missing}")
    return kind


def load_runs(path: str) -> list:
    """Existing run records of an append-only trajectory file; a
    pre-append-format file (one flat jnp-vs-pallas report with
    ``_meta``) becomes run #1.  An unparseable file raises instead of
    being overwritten — the whole point of the append format is never
    to lose the trajectory."""
    if not os.path.exists(path):
        return []
    with open(path) as f:
        try:
            data = json.load(f)
        except ValueError as e:
            raise SystemExit(
                f"{path} exists but is not valid JSON ({e}); refusing "
                f"to overwrite the perf trajectory — fix or move the "
                f"file and rerun") from e
    if isinstance(data, dict) and isinstance(data.get("runs"), list):
        return data["runs"]
    if isinstance(data, dict) and "runs" in data:
        # new-format marker with a mangled value: never "migrate" it.
        raise SystemExit(
            f"{path} has a 'runs' key that is not a list; refusing to "
            f"rewrite a damaged trajectory file")
    if isinstance(data, dict) and data:
        meta = data.pop("_meta", {})
        return [{"platform": meta.get("platform", "unknown"),
                 "git_sha": "pre-append-format",
                 "quick": meta.get("quick"),
                 "variants": data}]
    raise SystemExit(
        f"{path} holds unrecognized JSON (neither a runs list nor a "
        f"legacy report); refusing to overwrite it")


def append_serve_record(path: str, record: dict) -> int:
    """Validate ``record`` and append it to the BENCH_serve.json
    trajectory at ``path`` (atomic replace — a failure mid-dump must
    not truncate the file).  Returns the new run count."""
    validate_serve_record(record)
    runs = load_runs(path)
    runs.append(record)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"runs": runs}, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return len(runs)


SHARD_SWEEP = (1, 2, 4)


def shard_scaling_cell(variant: str, *, quick: bool = False,
                       backend: str = "jnp", lowering: str = "auto"):
    """Throughput vs num_shards for one variant — the horizontal-
    scaling record appended to BENCH_alloc.json (DESIGN.md §9).  Same
    heap and request stream at every shard count, so the axis isolates
    the sharded transaction schedule.  CPU caveat: the jnp path runs
    the serial (attempt, shard) replay host-side, so CPU cells GROW
    with num_shards — they are a correctness/trajectory record; the
    scaling result itself is a TPU measurement (gridded kernels)."""
    n = 128 if quick else 512
    cfg = HeapConfig(total_bytes=4 << 20, chunk_bytes=8 << 10,
                     min_page_bytes=16)
    out = {}
    for num_shards in SHARD_SWEEP:
        r = bench_variant(variant, n_allocs=n, size_bytes=256,
                          iters=4 if quick else ITERS, cfg=cfg,
                          backend=backend, lowering=lowering,
                          num_shards=num_shards)
        out[str(num_shards)] = {
            "backend": backend,
            "lowering": r["lowering"],
            "alloc_us_subsequent": r["alloc_us_subsequent"],
            "free_us_subsequent": r["free_us_subsequent"],
            "allocs_per_s_subsequent":
                1e6 * n / max(r["alloc_us_subsequent"], 1e-9),
            "data_ok": r["data_ok"],
        }
    return out
