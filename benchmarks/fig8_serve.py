"""Fig. 8 (ours): end-to-end serving throughput on the paged KV cache.

The paper's figures stop at allocator microbenchmarks; this figure
closes the loop the ROADMAP's north star asks for — the allocator
inside a decode hot path.  One cell = the serving engine generating a
fixed request batch to completion on the reduced qwen2 config through
the fused decode tick (serve/engine.py, DESIGN.md §11), reported as
tokens/second.

Methodology mirrors benchmarks/common.py: round 1 includes every jit
compile (the paper's avg-all column), round 2 replays the identical
request batch on the warm engine (avg-subsequent — the serving number
that matters).  CPU caveat as everywhere in this repo: pallas cells run
in interpret mode, so on CPU the jnp column is the perf signal and the
pallas column is a correctness/trajectory record.

``launches_per_tick`` rides along on EVERY cell — the launch count of
one fused tick read off its jaxpr (benchmarks/common.py delegates to
the engine, so stats and records always agree): 1 with
``alloc_backend="pallas"`` (the bulk grow transaction; attention is
the jnp paged path on the decode hot loop), 0 with the jnp oracle,
and constant in ``max_batch`` either way.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp


def _engine(backend: str, lowering: str, num_shards: int, quick: bool):
    from repro.configs import get_arch
    from repro.models.model import build_model
    from repro.serve.engine import ServingEngine

    cfg = get_arch("qwen2-0.5b").smoke()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServingEngine(m, params, max_batch=2 if quick else 4,
                        max_seq=96, kv_dtype=jnp.float32,
                        compute_dtype=jnp.float32,
                        alloc_backend=backend, alloc_lowering=lowering,
                        num_shards=num_shards)
    return cfg, eng


def _requests(cfg, quick: bool):
    rng = np.random.default_rng(0)
    n = 4 if quick else 8
    return [(rng.integers(2, cfg.vocab_size, int(rng.integers(8, 40))),
             8 if quick else 16) for _ in range(n)]


def serve_cell(*, backend: str = "jnp", lowering: str = "auto",
               num_shards: int = 1, quick: bool = False):
    """One serving-throughput measurement cell (see module doc)."""
    cfg, eng = _engine(backend, lowering, num_shards, quick)
    reqs = _requests(cfg, quick)

    def one_round():
        for prompt, max_new in reqs:
            eng.submit(prompt, max_new_tokens=max_new)
        t0 = time.perf_counter()
        done = eng.run_until_done(2000)
        dt = time.perf_counter() - t0
        toks = sum(len(r.out_tokens) for r in done)
        assert len(done) == len(reqs)
        return toks, dt

    toks1, dt1 = one_round()   # includes every jit compile
    toks2, dt2 = one_round()   # warm replay: the serving number
    row = {
        "variant": "serve/mega",
        "mode": "mega",
        "backend": backend,
        "lowering": eng.stats["alloc_lowering"],
        "num_shards": num_shards,
        "n": len(reqs), "size": toks2,
        "tokens": toks2,
        "tokens_per_s_all": toks1 / max(dt1, 1e-9),
        "tokens_per_s": toks2 / max(dt2, 1e-9),
        "alloc_txns": eng.stats["alloc_txns"],
        "launches_per_tick": eng.launches_per_tick(),
    }
    return row


def run(quick: bool = False, backend: str = "jnp",
        lowering: str = "auto", num_shards: int = 1):
    """Figure row: the fused tick on the requested backend."""
    return [serve_cell(backend=backend, lowering=lowering,
                       num_shards=num_shards, quick=quick)]


def serve_record(quick: bool = False):
    """The BENCH_serve.json cell block: the tick on the jnp oracle
    (the CPU perf signal) plus a mega/pallas cell for the fused-kernel
    trajectory and its launches-per-tick proof."""
    cells = {
        "mega/jnp": serve_cell(backend="jnp", quick=quick),
        "mega/pallas": serve_cell(backend="pallas", quick=quick),
    }
    return {k: {f: v[f] for f in ("tokens", "tokens_per_s_all",
                                  "tokens_per_s", "alloc_txns",
                                  "lowering", "launches_per_tick")}
            for k, v in cells.items()}
