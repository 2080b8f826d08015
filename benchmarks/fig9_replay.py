"""Fig. 9 (ours): traffic replay across the config zoo.

The paper's thesis is ONE dynamic allocator for heterogeneous
workloads; figs. 1-8 measure it on microbenchmarks and a single dense
decode path.  This figure drives the serving engine through realistic
traffic (serve/replay.py: Poisson arrivals, bursty spikes, mixed
length distributions, client abandonment) for one representative
config per model family — dense, MoE, SSM, enc-dec, and (full grid)
hybrid-recurrent and vision-language — with the per-modality page
policy routing SSM-state and MoE expert-buffer pages through the SAME
Ouroboros arena as KV pages (paged/kv_cache.modality_page_quota).

Every cell is a *pair*: the identical trace replays on a one-shard and
a four-shard allocator, token-for-token parity and end-state allocator
conservation are asserted inside (serve/replay.replay_pair), and BOTH
engines' telemetry is reported — p50/p99 tick latency, queue wait,
evictions, and the fragmentation/defrag trajectory.  A benchmark
row that prints has therefore already passed the engine's hardest
correctness check.

Not in the default figure list (it builds a model per family); run it
with ``--fig fig9_replay``, and add ``--serve-json BENCH_serve.json``
to append the cells as a ``replay`` record to the serving trajectory
(benchmarks/common.py schema helpers).  CPU caveat as everywhere:
tick-latency percentiles are trajectory records on CPU, perf signals
on a TPU backend.
"""
from __future__ import annotations

import dataclasses

#: family → representative arch.  The quick (CI nightly) grid replays
#: the first QUICK_FAMILIES families; the full grid replays all six.
FAMILIES = (
    ("dense", "qwen2-0.5b"),
    ("ssm", "mamba2-780m"),
    ("moe", "mixtral-8x7b"),
    ("encdec", "seamless-m4t-large-v2"),
    ("hybrid", "recurrentgemma-9b"),
    ("vlm", "qwen2-vl-2b"),
)
QUICK_FAMILIES = 2          # dense + one SSM: the nightly smoke pair
SCENARIO_NAMES = ("bursty", "abandon")
QUICK_SCENARIOS = ("steady", "abandon")


def _grid(quick: bool):
    fams = FAMILIES[:QUICK_FAMILIES] if quick else FAMILIES
    scs = QUICK_SCENARIOS if quick else SCENARIO_NAMES
    return fams, scs


_CELL_CACHE = {}        # the CSV rows and the --serve-json record
                        # share one grid computation per invocation


def replay_cells(quick: bool = False, backend: str = "jnp",
                 lowering: str = "auto"):
    """cell name ``family/arch/scenario/shards<S>`` → telemetry
    summary (serve/replay.ReplayResult.summary), for every (family,
    scenario) in the grid, on 1 and 4 shards.  Parity + conservation
    asserted per pair before its cells are admitted."""
    from repro.serve.replay import (SCENARIOS, engine_factory,
                                    generate_trace, replay_pair)

    key = (quick, backend, lowering)
    if key in _CELL_CACHE:
        return _CELL_CACHE[key]
    fams, scs = _grid(quick)
    cells = {}
    for fi, (family, arch) in enumerate(fams):
        cfg, make = engine_factory(arch)
        kw = dict(alloc_backend=backend, alloc_lowering=lowering)
        for si, name in enumerate(scs):
            sc = SCENARIOS[name]
            if quick:
                sc = dataclasses.replace(sc, n_requests=min(
                    sc.n_requests, 8))
            trace = generate_trace(sc, seed=101 * fi + si,
                                   vocab_size=cfg.vocab_size)
            pair = replay_pair(make(num_shards=1, **kw),
                               make(num_shards=4, **kw),
                               trace, scenario=name)
            for shards, r in zip((1, 4), pair):
                s = r.summary()
                s["family"], s["num_shards"] = family, shards
                cells[f"{family}/{arch}/{name}/shards{shards}"] = s
    _CELL_CACHE[key] = cells
    return cells


def run(quick: bool = False, backend: str = "jnp",
        lowering: str = "auto", num_shards: int = 1):
    """Figure rows for benchmarks/run.py's CSV printer: one row per
    (family, scenario, shard count) cell, ``us_per_call`` column = p99
    tick latency in ms (the tail is the serving headline, not the
    mean).  ``num_shards`` is not used: every pair replays on 1 and 4
    shards."""
    rows = []
    for name, cell in replay_cells(quick=quick, backend=backend,
                                   lowering=lowering).items():
        rows.append({
            "variant": f"replay/{name}",
            "backend": backend,
            "lowering": lowering,
            "n": cell["requests"],
            "size": cell["tokens"],
            **cell,
        })
    return rows


def replay_record(quick: bool = False):
    """The BENCH_serve.json ``replay`` cell block (jnp oracle — the
    CPU-meaningful column; pallas replays are covered by the engine's
    backend-parity tests)."""
    return replay_cells(quick=quick, backend="jnp")
